"""A decoder-only language model whose mixers are of two kinds, gated
delta-rule LINEAR attention in most layers and softmax grouped-query
attention in a few, with routed experts in every layer, built for SERVING
from a description: a dict of the model's own ``config.json`` keys (HF
``solar_open2`` naming: ``linear_attn_config``, ``gqa_layers``,
``use_gqa_gate``, ``kda_allow_neg_eigval``, ``n_routed_experts``).

    block:   h = x + Mixer_i(RMSNorm(x));   y = h + FFN(RMSNorm(h))
    FFN:     Shared(x) + scale * sum_i w_i Expert_i(x), top-k of E routed
             experts by sigmoid score plus a selection bias, no token dropped
    layer i in ``gqa_layers``: q, k, v, gate = u Wq, u Wk, u Wv, u Wg (no
             bias, NO positional encoding), causal softmax, grouped-query;
             out = (attn * sigmoid(gate)) Wo
    else:    [q | k | v] = silu(conv(u Wqkv)) (causal, depthwise,
             ``short_conv_kernel_size`` taps, no bias); q and k
             L2-normalised a head; log decay a KEY CHANNEL g = -exp(A_log_h)
             * softplus((u Wfa) Wfb + dt_bias); beta = 2 sigmoid(u Wb)
             (``kda_allow_neg_eigval``: else 1); a matrix state a head,
             S = (I - beta k k^T) Diag(exp g) S + beta k v^T; o = S^T q;
             out = (RMSNorm_head(o) * sigmoid((u Wga) Wgb + b_g)) Wo
    logits = RMSNorm(y_L) @ W_head, float32

A slot owns TWO kinds of state, and the builder declares both
(``geometry["state"]``): a ``gqa_layers`` layer has K and V page pools
``lad_k_<i>`` / ``lad_v_<i>`` ``[pages, page_size, kv_heads * head_dim]``
that grow with the sequence through the page table; a linear layer has
fixed-size arrays indexed by the slot itself, the matrix state
``lad_s_<i>`` ``[slots, heads, dk, dv]`` float32 (``dv`` on the lanes:
``kernels/delta_rule.py``) and the convolution's window ``lad_win_<i>``
``[taps - 1, slots, 2 heads dk + heads dv]`` of the ``q | k | v`` row.

With ``expert_shard`` (``{"of": E_all, "first": f}``) ``n_routed_experts``
counts the experts HELD here (``models/latent_moe_decoder.py`` has the
rule); ``vocab_size`` may be a slice of the published vocabulary.

The programs' frame (buckets, budget, rungs, feeds, sampler, fetches) is
``models/decoder_programs.py``'s. Here a prefill's delta rule walks each
prompt's REAL tokens in chunks and the state installed for its slot is the
one after its last real token; a reused slot's rows are overwritten whole.
In a step the state arrays and the pools are donated and updated in place;
a slot that is not live keeps its state rows.

The matrices are stored input-major; the three projections and the three
convolutions of a linear layer are stored as ONE ``q | k | v`` matrix and
one ``[taps, q | k | v]`` weight: a checkpoint's loader concatenates once.
"""

import collections
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.delta_rule import CHUNK
from paddle_tpu.models import decoder_programs

__all__ = ["linear_dims", "check_served", "layer_kinds", "parameter_shapes",
           "random_parameters", "load_parameters",
           "build_linear_attn_moe_decoder"]

LINEAR, GQA = "linear_attention", "gqa"


def check_served(desc):
    """Refuse, by the key at fault, a description whose keys ask for what
    this builder does not serve (``linear_dims`` and the session's
    ``builder_for`` both ask)."""
    lin = desc["linear_attn_config"]
    for key, value, served, why in (
            ("first_k_dense_replace", desc.get("first_k_dense_replace", 0),
             0, "every layer's feed-forward is routed experts"),
            ("linear_attn_config.num_kv_heads", lin.get("num_kv_heads"),
             None, "a linear layer has as many value heads as heads"),
            ("kda_use_full_proj", bool(desc.get("kda_use_full_proj", False)),
             False, "the decay and output gates are low-rank"),
            ("use_rope", bool(desc.get("use_rope", False)), False,
             "no layer has a positional encoding"),
            ("use_gqa_gate", bool(desc.get("use_gqa_gate", True)), True,
             "the attention layers' output is gated"),
            ("tie_word_embeddings",
             bool(desc.get("tie_word_embeddings", False)), False,
             "the head is its own matrix"),
            ("n_group", desc.get("n_group", 1), 1,
             "one routing group"),
            ("topk_group", desc.get("topk_group", 1), 1,
             "one routing group")):
        if value != served:
            raise NotImplementedError(
                "%s=%r: only %r is built (%s)" % (key, value, served, why))


def linear_dims(desc):
    """The sizes the programs are built from, by the config's keys."""
    check_served(desc)
    lin = desc["linear_attn_config"]
    d = dict(
        D=int(desc["hidden_size"]), H=int(desc["num_attention_heads"]),
        Hkv=int(desc["num_key_value_heads"]), dh=int(desc["head_dim"]),
        Hl=int(lin["num_heads"]), dl=int(lin["head_dim"]),
        kw=int(lin["short_conv_kernel_size"]),
        Fm=int(desc["moe_intermediate_size"]),
        E=int(desc["n_routed_experts"]), k=int(desc["num_experts_per_tok"]),
        shared=int(desc.get("n_shared_experts", 0)),
        L=int(desc["num_hidden_layers"]), V=int(desc["vocab_size"]),
        eps=float(desc.get("rms_norm_eps", 1e-5)),
        scale=float(desc.get("routed_scaling_factor", 1.0)),
        norm_topk=bool(desc.get("norm_topk_prob", True)),
        beta_scale=2.0 if desc.get("kda_allow_neg_eigval", False) else 1.0)
    if d["H"] % d["Hkv"]:
        raise ValueError("%d query heads over %d key/value heads: heads "
                         "must divide" % (d["H"], d["Hkv"]))
    shard = desc.get("expert_shard")
    d["Er"] = int(shard["of"]) if shard else d["E"]
    d["first"] = int(shard["first"]) if shard else None
    if shard and not 0 <= d["first"] <= d["Er"] - d["E"]:
        raise ValueError(
            "expert_shard %r: n_routed_experts=%d experts from `first` do "
            "not lie among its `of`" % (shard, d["E"]))
    d["lw"] = d["Hl"] * d["dl"]        # a linear layer's q, k or v row
    return d


def layer_kinds(desc):
    """``"gqa"`` or ``"linear_attention"`` for every layer."""
    gqa = set(int(i) for i in desc["gqa_layers"])
    return [GQA if i in gqa else LINEAR
            for i in range(int(desc["num_hidden_layers"]))]


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order. The
    router's selection bias, ``a_log`` and ``dt_bias`` are float32
    whatever ``dtype`` is."""
    d = linear_dims(desc)
    D, lw, dl = d["D"], d["lw"], d["dl"]
    qw, row = d["H"] * d["dh"], d["Hkv"] * d["dh"]
    out = collections.OrderedDict()

    def add(name, *shape, **kw):
        out[name] = (tuple(shape), kw.get("dtype", dtype))

    add("lad_embed", d["V"], D)
    for i, kind in enumerate(layer_kinds(desc)):
        p = "lad_%d_" % i
        add(p + "in_norm", D)
        if kind == GQA:
            add(p + "q", D, qw)
            add(p + "k", D, row)
            add(p + "v", D, row)
            add(p + "gate", D, qw)
            add(p + "o", qw, D)
        else:
            add(p + "qkv", D, 3 * lw)
            add(p + "conv_w", d["kw"], 3 * lw)
            add(p + "f_a", D, dl)
            add(p + "f_b", dl, lw)
            add(p + "dt_bias", lw, dtype="float32")
            add(p + "a_log", d["Hl"], dtype="float32")
            add(p + "beta", D, d["Hl"])
            add(p + "g_a", D, dl)
            add(p + "g_b", dl, lw)
            add(p + "g_bias", lw)
            add(p + "o_norm", dl)
            add(p + "o", lw, D)
        add(p + "ff_norm", D)
        add(p + "router", D, d["Er"])
        add(p + "router_bias", d["Er"], dtype="float32")
        add(p + "experts_gate", d["E"], D, d["Fm"])
        add(p + "experts_up", d["E"], D, d["Fm"])
        add(p + "experts_down", d["E"], d["Fm"], D)
        if d["shared"]:
            Fs = d["Fm"] * d["shared"]
            add(p + "shared_gate", D, Fs)
            add(p + "shared_up", D, Fs)
            add(p + "shared_down", Fs, D)
    add("lad_final_norm", D)
    add("lad_head", D, d["V"])
    return out


def random_parameters(desc, seed=0, dtype="float32"):
    """Seeded parameters for tests and examples (host arrays): matrices
    ``N(0, 1/fan_in)``, norm scales near 1, the selection bias uniform in
    +-0.01, ``a_log = log(U(1, 16))`` a head and ``dt_bias`` the inverse
    softplus of a log-uniform 1e-3..1e-1 (the public initialisers)."""
    rng = np.random.RandomState(seed)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("router_bias"):
            v = rng.uniform(-0.01, 0.01, shape)
        elif name.endswith("a_log"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("dt_bias"):
            delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            v = delta + np.log(-np.expm1(-delta))          # softplus^-1
        elif name.endswith("g_bias"):
            v = 0.1 * rng.standard_normal(shape)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("conv_w"):
            v = rng.standard_normal(shape) * shape[0] ** -0.5
        elif name == "lad_embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) * shape[-2] ** -0.5
        out[name] = np.asarray(v, "float32").astype(np_dtype(dt))
    return out


load_parameters = functools.partial(decoder_programs.load_parameters,
                                    parameter_shapes)


def _family(desc, dtype, tokens_per_dispatch):
    """This family's layers and state (``decoder_programs.DecoderFamily``);
    ``geometry["prefill_chunk"]`` is the tokens a chunk of the delta
    rule's prefill walks."""
    nn = fluid.layers
    d = linear_dims(desc)
    kinds = layer_kinds(desc)
    row, lw, Hl, dl = d["Hkv"] * d["dh"], d["lw"], d["Hl"], d["dl"]

    def state(S, P, ps, npp):
        page_pools, slot_arrays = collections.OrderedDict(), \
            collections.OrderedDict()
        for i, kind in enumerate(kinds):
            if kind == GQA:
                for part in "kv":
                    page_pools["lad_%s_%d" % (part, i)] = {
                        "shape": (P, ps, row), "dtype": dtype}
            else:
                slot_arrays["lad_s_%d" % i] = {
                    "shape": (S, Hl, dl, dl), "dtype": "float32",
                    "slot_axis": 0}
                slot_arrays["lad_win_%d" % i] = {
                    "shape": (d["kw"] - 1, S, 3 * lw), "dtype": dtype,
                    "slot_axis": 1}
        return {"page_pools": page_pools, "slot_arrays": slot_arrays}

    def blocks(f, x, attend, mix):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, k, v)`` writes an attention layer's rows and
        attends, ``mix(i, qkv, g, beta, w)`` runs a linear layer's
        convolution and delta rule (``w(part)``: its parameters). Returns
        (x, chosen per layer, tokens per held expert per layer)."""
        w = f.w
        chosen, counts = [], []
        for i, kind in enumerate(kinds):
            p = "lad_%d_" % i
            nx = nn.rms_norm(x, w(p + "in_norm"), d["eps"])
            if kind == GQA:
                q, k, v, gate = [nn.dense_projection(nx, w(p + part))
                                 for part in ("q", "k", "v", "gate")]
                y = nn.sigmoid_gate(attend(i, q, k, v), gate)
            else:
                def low_rank(a, b, out_dtype="input", nx=nx, p=p):
                    return nn.dense_projection(
                        nn.dense_projection(nx, w(p + a)), w(p + b),
                        out_dtype=out_dtype)

                g, beta = nn.delta_rule_gates(
                    low_rank("f_a", "f_b", "float32"), w(p + "dt_bias"),
                    w(p + "a_log"),
                    nn.dense_projection(nx, w(p + "beta"),
                                        out_dtype="float32"),
                    heads=Hl, beta_scale=d["beta_scale"])
                o = mix(i, nn.dense_projection(nx, w(p + "qkv")), g, beta,
                        lambda part, p=p: w(p + part))
                gate = nn.elementwise_add(low_rank("g_a", "g_b"),
                                          w(p + "g_bias"))
                y = nn.gated_head_norm(o, w(p + "o_norm"), gate, heads=Hl,
                                       epsilon=d["eps"])
            x = nn.elementwise_add(x, nn.dense_projection(y, w(p + "o")))
            nx = nn.rms_norm(x, w(p + "ff_norm"), d["eps"])
            shared = ((w(p + "shared_gate"), w(p + "shared_up"),
                       w(p + "shared_down")) if d["shared"] else None)
            ff, ch, cnt = nn.dropless_moe_ffn(
                nx, w(p + "router"), w(p + "router_bias"),
                w(p + "experts_gate"), w(p + "experts_up"),
                w(p + "experts_down"), shared=shared, valid=f.valid,
                top_k=d["k"], norm_topk=d["norm_topk"], scale=d["scale"],
                held_first=d["first"])
            chosen.append(ch)
            counts.append(cnt)
            x = nn.elementwise_add(x, ff)
        return nn.rms_norm(x, w("lad_final_norm"), d["eps"]), chosen, counts

    def no_bias():
        # the convolution of ``ssm_ops`` takes a bias; this model has none
        return nn.fill_constant([3 * lw], dtype, 0.0)

    def prefill(f, x):
        def attend(i, q, k, v):
            nn.latent_row_prefill(f.state["lad_k_%d" % i], k,
                                  f.page_rows, f.lens)
            nn.latent_row_prefill(f.state["lad_v_%d" % i], v,
                                  f.page_rows, f.lens)
            # the flash kernel at the long buckets' tiles; no band
            return nn.window_prefill_attention(
                q, k, v, prompts=f.rows, heads=d["H"], kv_heads=d["Hkv"],
                window=0)

        def mix(i, qkv, g, beta, w):
            qkv, window = nn.ssm_causal_conv(
                qkv, w("conv_w"), no_bias(), f.lens)
            q, k, v = nn.split(qkv, 3, dim=-1)
            o, last = nn.delta_rule_prefill(q, k, v, g, beta, f.lens)
            nn.slot_state_write(f.state["lad_s_%d" % i], f.slot_idx,
                                last, axis=0)
            nn.slot_state_write(f.state["lad_win_%d" % i], f.slot_idx,
                                window, axis=1)
            return o

        x, chosen, _counts = blocks(f, x, attend, mix)
        return x, [("first_chosen", chosen)]

    def step(f, x):
        def attend(i, q, k, v):
            k_pool, v_pool = f.state["lad_k_%d" % i], \
                f.state["lad_v_%d" % i]
            nn.latent_row_write(k_pool, k, f.table, f.pos)
            nn.latent_row_write(v_pool, v, f.table, f.pos)
            return nn.gqa_paged_attention(q, k_pool, v_pool, f.table,
                                          f.lengths, heads=d["H"])

        def mix(i, qkv, g, beta, w):
            qkv = nn.ssm_conv_step(f.state["lad_win_%d" % i], qkv,
                                   w("conv_w"), no_bias(), f.live)
            q, k, v = nn.split(qkv, 3, dim=-1)
            return nn.delta_rule_state_update(
                f.state["lad_s_%d" % i], q, k, v, g, beta, f.live)

        x, chosen, counts = blocks(f, x, attend, mix)
        return x, [("chosen", chosen), ("expert_tokens", counts)]

    return decoder_programs.DecoderFamily(
        "lad", parameter_shapes(desc, dtype), d["V"], state, prefill,
        step, geometry={
            "row_width": row, "layer_kinds": kinds,
            "moe_layers": list(range(d["L"])),
            # the tokens a chunk of the linear layers' prefill walks
            "prefill_chunk": CHUNK,
            # the experts held of those routed among, a token's choices
            "experts": {"held": d["E"], "of": d["Er"], "top_k": d["k"]}})


build_linear_attn_moe_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
