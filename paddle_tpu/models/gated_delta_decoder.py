"""A DENSE decoder-only language model whose mixers are of two kinds, Gated
DeltaNet LINEAR attention (arXiv:2412.06464) in most layers and plain
multi-head softmax attention in a few, built for SERVING from a
description: a dict of the model's own ``config.json`` keys, HF
``olmo_hybrid``'s (``layer_types`` of ``linear_attention`` /
``full_attention`` with the ``linear_*`` keys HF ``qwen3_next`` names the
same layer by). No layer has an expert or a router.

    block:   h = x + RMSNorm_a(Mixer_i(x));   y = h + RMSNorm_f(SwiGLU(h))
             (the Olmo 2 / Olmo 3 convention: the norm is on a sub-block's
             OUTPUT, and a sub-block reads the residual stream as it is)
    SwiGLU:  (silu(x Wg) * (x Wu)) Wd at ``intermediate_size``
    full:    q = RMSNorm_q(x Wq), k = RMSNorm_k(x Wk), the norm over the
             WHOLE projected row (scale ``[heads * head_dim]``), v = x Wv;
             multi-head (as many key/value heads as heads), causal softmax
             at head_dim^-1/2, NO positional encoding (the linear layers
             carry position); out = attn Wo, no gate, no bias
    linear:  [q | k | v] = silu(conv(x Wqkv)) (causal, depthwise,
             ``linear_conv_kernel_dim`` taps, no bias), q and k ``H x dk``
             wide and v ``H x dv`` (``dk`` 96 beside ``dv`` 192); q and k
             L2-normalised a head, q scaled by dk^-1/2; ONE log decay a
             head a token, g = -exp(A_log_h) * softplus(x Wa + dt_bias_h);
             beta = 2 sigmoid(x Wb) (``linear_allow_neg_eigval``: else 1);
             a matrix state a head, S = (I - beta k k^T) exp(g) S + beta k
             v^T; o = S^T q; out = (RMSNorm_head(o) * silu(x Wgate)) Wo, the
             gate of full rank
    logits = RMSNorm(y_L) @ W_head, float32

A slot owns TWO kinds of state, and the builder declares both
(``geometry["state"]``): a full layer has K and V page pools ``gdd_k_<i>`` /
``gdd_v_<i>`` ``[pages, page_size, heads * head_dim]``, which grow with the
sequence through the page table; a linear layer has fixed-size arrays
indexed by the slot itself, the float32 matrix state ``gdd_s_<i>``
``[slots, H / pack, dk, pack * dv]`` and the convolution's window
``gdd_win_<i>`` ``[taps - 1, slots, 2 H dk + H dv]`` of the ``q | k | v``
row. ``pack`` heads' value lanes lie side by side in a tile of the state
(``kernels/delta_rule.py``: ``pack_heads``) so that the lanes are whole
128-lane tiles and the array is no larger than its elements: 2 where ``dv``
alone is not a lane multiple and two of them are (192), else 1.
``geometry["state_bytes_slot_layer"]`` is what a slot's state takes in one
linear layer and ``geometry["kv_row_bytes"]`` a position's K and V rows in
one full layer: a reader turns the round's ``state_slots_live`` and
``kv_rows_visible`` into bytes with them.

The programs' frame (buckets, budget, rungs, feeds, sampler, fetches) is
``models/decoder_programs.py``'s; the delta rule's prefill walks each
prompt's REAL tokens in chunks and a step donates and updates the arrays
in place, as ``models/linear_attn_moe_decoder.py``'s does (its layers are
Kimi Delta Attention in pre-norm blocks with routed experts: other
equations, the same kernels and ops). The sub-blocks are built under
``fluid.name_scope`` (``gdn_mixer``, ``mha_attention``, ``dense_ffn``): a
compiled program's instructions say which they are.

The matrices are stored input-major; the three projections and the three
convolutions of a linear layer are ONE ``q | k | v`` matrix and one
``[taps, q | k | v]`` weight: a checkpoint's loader concatenates once.
"""

import collections
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.delta_rule import CHUNK
from paddle_tpu.models import decoder_programs

__all__ = ["dims", "check_served", "parameter_shapes", "random_parameters",
           "load_parameters", "build_gated_delta_decoder"]

LINEAR, FULL = "linear_attention", "full_attention"


def check_served(desc):
    """Refuse, by the key at fault, a description whose keys ask for what
    this builder does not serve (``dims`` and the session's
    ``builder_for`` both ask)."""
    rope = (desc.get("rope_parameters") or {}).get("rope_theta")
    for key, value, served, why in (
            ("tie_word_embeddings",
             bool(desc.get("tie_word_embeddings", False)), False,
             "the head is its own matrix"),
            ("attention_bias", bool(desc.get("attention_bias", False)),
             False, "no projection has a bias"),
            ("rope_parameters.rope_theta", rope, None,
             "no layer has a positional encoding"),
            ("hidden_act", desc.get("hidden_act", "silu"), "silu",
             "the feed-forward is a SwiGLU"),
            ("linear_num_value_heads", desc["linear_num_value_heads"],
             desc["linear_num_key_heads"],
             "a linear layer has as many value heads as key heads"),
            ("num_key_value_heads",
             desc.get("num_key_value_heads", desc["num_attention_heads"]),
             desc["num_attention_heads"],
             "a full layer is multi-head attention")):
        if value != served:
            raise NotImplementedError(
                "%s=%r: only %r is built (%s)" % (key, value, served, why))
    kinds = list(desc["layer_types"])
    if len(kinds) != int(desc["num_hidden_layers"]):
        raise ValueError("layer_types names %d layers, num_hidden_layers "
                         "is %d" % (len(kinds), desc["num_hidden_layers"]))
    for i, kind in enumerate(kinds):
        if kind not in (LINEAR, FULL):
            raise NotImplementedError(
                "layer_types[%d]=%r: only %r and %r are built"
                % (i, kind, LINEAR, FULL))


def dims(desc):
    """The sizes the programs are built from, by the config's keys."""
    check_served(desc)
    D, H = int(desc["hidden_size"]), int(desc["num_attention_heads"])
    d = dict(
        D=D, H=H, dh=int(desc.get("head_dim") or D // H),
        Hl=int(desc["linear_num_key_heads"]),
        dk=int(desc["linear_key_head_dim"]),
        dv=int(desc["linear_value_head_dim"]),
        kw=int(desc["linear_conv_kernel_dim"]),
        F=int(desc["intermediate_size"]),
        L=int(desc["num_hidden_layers"]), V=int(desc["vocab_size"]),
        eps=float(desc.get("rms_norm_eps", 1e-6)),
        beta_scale=2.0 if desc.get("linear_allow_neg_eigval", False)
        else 1.0)
    d["row"] = H * d["dh"]                  # a full layer's q, k or v row
    d["qk"], d["vw"] = d["Hl"] * d["dk"], d["Hl"] * d["dv"]
    d["lw"] = 2 * d["qk"] + d["vw"]         # a linear layer's q | k | v row
    # heads a tile of the state: values that are not a lane multiple alone
    # and are one in pairs lie two and two
    d["pack"] = 2 if (d["dv"] % 128 and (2 * d["dv"]) % 128 == 0
                      and d["Hl"] % 2 == 0) else 1
    return d


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order;
    ``a_log`` and ``dt_bias`` are float32 whatever ``dtype`` is."""
    d = dims(desc)
    D = d["D"]
    out = collections.OrderedDict()

    def add(name, *shape, **kw):
        out[name] = (tuple(shape), kw.get("dtype", dtype))

    add("gdd_embed", d["V"], D)
    for i, kind in enumerate(desc["layer_types"]):
        p = "gdd_%d_" % i
        if kind == FULL:
            for part in "qkv":
                add(p + part, D, d["row"])
            add(p + "q_norm", d["row"])
            add(p + "k_norm", d["row"])
            add(p + "o", d["row"], D)
        else:
            add(p + "qkv", D, d["lw"])
            add(p + "conv_w", d["kw"], d["lw"])
            add(p + "a", D, d["Hl"])
            add(p + "dt_bias", d["Hl"], dtype="float32")
            add(p + "a_log", d["Hl"], dtype="float32")
            add(p + "beta", D, d["Hl"])
            add(p + "gate", D, d["vw"])
            add(p + "o_norm", d["dv"])
            add(p + "o", d["vw"], D)
        add(p + "attn_norm", D)
        add(p + "ffn_gate", D, d["F"])
        add(p + "ffn_up", D, d["F"])
        add(p + "ffn_down", d["F"], D)
        add(p + "ff_norm", D)
    add("gdd_final_norm", D)
    add("gdd_head", D, d["V"])
    return out


def random_parameters(desc, seed=0, dtype="float32"):
    """Seeded parameters for tests and examples (host arrays): matrices
    ``N(0, 1/fan_in)``, norm scales near 1, ``a_log = log(U(1, 16))`` a
    head and ``dt_bias`` the inverse softplus of a log-uniform 1e-3..1e-1
    (the public layer's initialisers)."""
    rng = np.random.RandomState(seed)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("a_log"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("dt_bias"):
            delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            v = delta + np.log(-np.expm1(-delta))          # softplus^-1
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("conv_w"):
            v = rng.standard_normal(shape) * shape[0] ** -0.5
        elif name == "gdd_embed":
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
    d = dims(desc)
    kinds = list(desc["layer_types"])
    Hl, pack, eps = d["Hl"], d["pack"], d["eps"]
    state_shape = (Hl // pack, d["dk"], pack * d["dv"])
    itemsize = np.dtype(np_dtype(dtype)).itemsize

    def state(S, P, ps, npp):
        page_pools, slot_arrays = collections.OrderedDict(), \
            collections.OrderedDict()
        for i, kind in enumerate(kinds):
            if kind == FULL:
                for part in "kv":
                    page_pools["gdd_%s_%d" % (part, i)] = {
                        "shape": (P, ps, d["row"]), "dtype": dtype}
            else:
                slot_arrays["gdd_s_%d" % i] = {
                    "shape": (S,) + state_shape, "dtype": "float32",
                    "slot_axis": 0}
                slot_arrays["gdd_win_%d" % i] = {
                    "shape": (d["kw"] - 1, S, d["lw"]), "dtype": dtype,
                    "slot_axis": 1}
        return {"page_pools": page_pools, "slot_arrays": slot_arrays}

    def blocks(f, x, attend, mix):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, k, v)`` writes a full layer's rows and attends,
        ``mix(i, qkv, g, beta, w)`` runs a linear layer's convolution and
        delta rule (``w(part)``: its parameters)."""
        for i, kind in enumerate(kinds):
            def w(part, p="gdd_%d_" % i):
                return f.w(p + part)

            if kind == FULL:
                with fluid.name_scope("mha_attention"):
                    q, k = [nn.rms_norm(nn.dense_projection(x, w(part)),
                                        w(part + "_norm"), eps)
                            for part in "qk"]
                    y = attend(i, q, k, nn.dense_projection(x, w("v")))
                    y = nn.dense_projection(y, w("o"))
            else:
                with fluid.name_scope("gdn_mixer"):
                    g, beta = nn.delta_rule_gates(
                        nn.dense_projection(x, w("a"), out_dtype="float32"),
                        w("dt_bias"), w("a_log"),
                        nn.dense_projection(x, w("beta"),
                                            out_dtype="float32"),
                        heads=Hl, beta_scale=d["beta_scale"])
                    o = mix(i, nn.dense_projection(x, w("qkv")), g, beta, w)
                    y = nn.dense_projection(nn.gated_head_norm(
                        o, w("o_norm"), nn.dense_projection(x, w("gate")),
                        heads=Hl, epsilon=eps, gate_act="silu"), w("o"))
            x = nn.elementwise_add(x, nn.rms_norm(y, w("attn_norm"), eps))
            with fluid.name_scope("dense_ffn"):
                y = nn.gated_ffn(x, w("ffn_gate"), w("ffn_up"),
                                 w("ffn_down"))
            x = nn.elementwise_add(x, nn.rms_norm(y, w("ff_norm"), eps))
        return nn.rms_norm(x, f.w("gdd_final_norm"), eps), []

    def no_bias():
        # the convolution of ``ssm_ops`` takes a bias; this model has none
        return nn.fill_constant([d["lw"]], dtype, 0.0)

    def qkv_of(row):
        return nn.split(row, [d["qk"], d["qk"], d["vw"]], dim=-1)

    def prefill(f, x):
        def attend(i, q, k, v):
            nn.latent_row_prefill(f.state["gdd_k_%d" % i], k,
                                  f.page_rows, f.lens)
            nn.latent_row_prefill(f.state["gdd_v_%d" % i], v,
                                  f.page_rows, f.lens)
            # the flash kernel at the long buckets' tiles; no band
            return nn.window_prefill_attention(
                q, k, v, prompts=f.rows, heads=d["H"], kv_heads=d["H"],
                window=0)

        def mix(i, qkv, g, beta, w):
            qkv, window = nn.ssm_causal_conv(
                qkv, w("conv_w"), no_bias(), f.lens)
            q, k, v = qkv_of(qkv)
            o, last = nn.delta_rule_prefill(q, k, v, g, beta, f.lens,
                                            state_pack=pack)
            nn.slot_state_write(f.state["gdd_s_%d" % i], f.slot_idx,
                                last, axis=0)
            nn.slot_state_write(f.state["gdd_win_%d" % i], f.slot_idx,
                                window, axis=1)
            return o

        return blocks(f, x, attend, mix)

    def step(f, x):
        def attend(i, q, k, v):
            k_pool, v_pool = f.state["gdd_k_%d" % i], \
                f.state["gdd_v_%d" % i]
            nn.latent_row_write(k_pool, k, f.table, f.pos)
            nn.latent_row_write(v_pool, v, f.table, f.pos)
            # multi-head is the grouped-query kernel at a group of one
            return nn.gqa_paged_attention(q, k_pool, v_pool, f.table,
                                          f.lengths, heads=d["H"])

        def mix(i, qkv, g, beta, w):
            qkv = nn.ssm_conv_step(f.state["gdd_win_%d" % i], qkv,
                                   w("conv_w"), no_bias(), f.live)
            q, k, v = qkv_of(qkv)
            return nn.delta_rule_state_update(
                f.state["gdd_s_%d" % i], q, k, v, g, beta, f.live)

        return blocks(f, x, attend, mix)

    return decoder_programs.DecoderFamily(
        "gdd", parameter_shapes(desc, dtype), d["V"], state, prefill,
        step, mask=False, geometry={
            "row_width": d["row"], "layer_kinds": kinds,
            # the tokens a chunk of the linear layers' prefill walks
            "prefill_chunk": CHUNK, "state_pack": pack,
            # a slot's matrix state in ONE linear layer, and a position's
            # K and V rows in ONE full layer
            "state_bytes_slot_layer": 4 * int(np.prod(state_shape)),
            "kv_row_bytes": 2 * d["row"] * itemsize})


build_gated_delta_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
