"""A decoder-only language model with latent (MLA) attention and routed
experts, built for SERVING from a description: a dict of the model's own
``config.json`` keys (HF ``glm4_moe_lite`` / ``deepseek_v3`` naming).

    block:  h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    FFN:    SwiGLU in the first ``first_k_dense_replace`` layers, then
            Shared(x) + scale * sum_i w_i Expert_i(x), top-k of E routed
            experts by sigmoid score plus a selection bias, no token dropped
    MLA:    the cache holds one row ``[RMSNorm(ckv) | RoPE(k_rope)]`` a
            token a layer, ``kv_lora_rank + qk_rope_head_dim`` wide, read
            by every head; decode attends in the absorbed form
            (``kernels/latent_attention.py``), prefill in the expanded
            form through the flash kernel

With ``index_topk`` in the description (HF ``glm_moe_dsa``, the DeepSeek
sparse attention of the ``deepseek_v32`` modelling code) attention is
LEARNED SPARSE attention: a layer whose ``indexer_types`` entry is
``full`` has an indexer (``index_n_heads`` queries from the compressed
query, one ``index_head_dim``-wide key a position kept in a NARROW pool
``lmd_ipool_<i>`` under the same page table, a weight a head) that chooses
the ``index_topk`` positions each query attends; a ``shared`` layer has no
indexer and no narrow pool and takes the choice of the nearest ``full``
layer before it. Decode gathers the chosen rows from the paged pool
(``kernels/sparse_latent_attention.py``); a prefill bucket longer than
``index_topk`` applies the choice as a mask to the expanded form. With
``expert_shard`` (``{"of": E_all, "first": f}``) ``n_routed_experts``
counts the experts HELD here, ``f .. f + n_routed_experts - 1`` of the
``E_all`` the router chooses among: the router keeps every output and its
top-k, the expert op computes the held experts' part of the sum and leaves
the rest out. ``vocab_size`` may be a slice of the published vocabulary:
embedding, head and sampling are over what it says.

State: a pool of latent rows a layer, ``lmd_pool_<i>`` ``[pages,
page_size, pool_width(row)]`` (the row rounded up to whole lanes, 576 ->
640: the lanes past the row stay zero). The programs' frame (buckets,
budget, rungs, feeds, sampler, fetches) is ``models/decoder_programs.py``'s.
"""

import collections
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.latent_attention import pool_width
from paddle_tpu.models import decoder_programs

__all__ = ["decoder_dims", "parameter_shapes", "random_parameters",
           "load_parameters", "build_latent_moe_decoder"]


def decoder_dims(desc):
    """The sizes the programs are built from, by the config's keys."""
    check_served(desc)
    d = dict(
        D=int(desc["hidden_size"]), H=int(desc["num_attention_heads"]),
        dn=int(desc["qk_nope_head_dim"]), dr=int(desc["qk_rope_head_dim"]),
        dv=int(desc["v_head_dim"]), rq=int(desc["q_lora_rank"]),
        C=int(desc["kv_lora_rank"]), F=int(desc["intermediate_size"]),
        Fm=int(desc["moe_intermediate_size"]),
        E=int(desc["n_routed_experts"]), k=int(desc["num_experts_per_tok"]),
        shared=int(desc.get("n_shared_experts", 0)),
        dense=int(desc.get("first_k_dense_replace", 0)),
        L=int(desc["num_hidden_layers"]), V=int(desc["vocab_size"]),
        eps=float(desc.get("rms_norm_eps", 1e-5)),
        theta=float(desc.get("rope_theta") or desc.get(
            "rope_parameters", {}).get("rope_theta", 10000.0)),
        scale=float(desc.get("routed_scaling_factor", 1.0)),
        norm_topk=bool(desc.get("norm_topk_prob", True)),
        interleave=bool(desc.get("rope_interleave", False)))
    # a shard of the experts: E held of Er routed among, from ``first``
    shard = desc.get("expert_shard")
    d["Er"] = int(shard["of"]) if shard else d["E"]
    d["first"] = int(shard["first"]) if shard else None
    if shard and not 0 <= d["first"] <= d["Er"] - d["E"]:
        raise ValueError(
            "expert_shard %r: n_routed_experts=%d experts from `first` do "
            "not lie among its `of`" % (shard, d["E"]))
    _sparse_dims(desc, d)
    d["W"] = d["C"] + d["dr"]          # the cached row
    d["Wp"] = pool_width(d["W"])       # as the pool holds it
    return d


def check_served(desc):
    """Refuse, by the key at fault, a description whose keys ask for what
    this builder does not serve (``decoder_dims`` and the session's
    ``builder_for`` both ask; only the keys that are there are read)."""
    if "q_lora_rank" in desc and desc["q_lora_rank"] is None:
        raise NotImplementedError(
            "q_lora_rank=None: only a compressed query (q_a, its norm, "
            "q_b) is built here; a latent layer whose query comes straight "
            "from the normed input is `linear_attn_moe_decoder`'s, beside "
            "`linear_attn_config`")
    if desc.get("n_group", 1) != 1 or desc.get("topk_group", 1) != 1:
        raise NotImplementedError(
            "group-limited routing (n_group=%r, topk_group=%r): only one "
            "group is built" % (desc.get("n_group"), desc.get("topk_group")))
    if not desc.get("index_topk"):
        return
    if desc.get("index_topk_pattern") is not None:
        raise NotImplementedError(
            "index_topk_pattern=%r: one index_topk for every layer is "
            "built" % (desc["index_topk_pattern"],))
    types = list(desc.get("indexer_types") or ["full"])
    if set(types) - {"full", "shared"}:
        raise NotImplementedError(
            "indexer_types=%r: each is `full` or `shared`" % (types,))
    if types[0] != "full":
        raise NotImplementedError(
            "indexer_types starts with %r: a `shared` layer takes the "
            "choice of a `full` layer BEFORE it, and the first has none"
            % types[0])


def _sparse_dims(desc, d):
    """The keys of learned sparse attention and of the layers' kinds:
    ``d["topk"]`` (0: dense attention), the indexer's sizes and
    ``d["indexer"]``, per layer ``"full"`` / ``"shared"`` (``check_served``
    has refused what is not built)."""
    L = d["L"]
    kinds = desc.get("mlp_layer_types")
    if kinds is not None:
        want = ["dense"] * d["dense"] + ["sparse"] * (L - d["dense"])
        if list(kinds) != want:
            raise NotImplementedError(
                "mlp_layer_types=%r: only `first_k_dense_replace` (%d) "
                "leading dense layers before the sparse ones are built, "
                "one entry a layer" % (list(kinds), d["dense"]))
    d["topk"] = int(desc.get("index_topk") or 0)
    d["indexer"] = []
    if not d["topk"]:
        return
    types = list(desc.get("indexer_types") or ["full"] * L)
    if len(types) != L:
        raise NotImplementedError(
            "indexer_types=%r: one entry a layer (%d)" % (types, L))
    d.update(indexer=types, J=int(desc["index_n_heads"]),
             dI=int(desc["index_head_dim"]),
             idx_interleave=bool(desc.get("indexer_rope_interleave", True)))


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order. The
    router's selection bias is float32 whatever ``dtype`` is."""
    d = decoder_dims(desc)
    D, H = d["D"], d["H"]
    out = collections.OrderedDict()

    def add(name, *shape, **kw):
        out[name] = (tuple(shape), kw.get("dtype", dtype))

    add("lmd_embed", d["V"], D)
    for i in range(d["L"]):
        p = "lmd_%d_" % i
        add(p + "attn_norm", D)
        add(p + "q_a", D, d["rq"])
        add(p + "q_norm", d["rq"])
        add(p + "q_b", d["rq"], H * (d["dn"] + d["dr"]))
        add(p + "kv_a", D, d["W"])
        add(p + "kv_norm", d["C"])
        add(p + "kv_b", d["C"], H * (d["dn"] + d["dv"]))
        add(p + "o", H * d["dv"], D)
        if d["topk"] and d["indexer"][i] == "full":
            add(p + "idx_q", d["rq"], d["J"] * d["dI"])
            add(p + "idx_k", D, d["dI"])
            add(p + "idx_k_norm", d["dI"])
            add(p + "idx_k_shift", d["dI"])
            add(p + "idx_w", D, d["J"])
        add(p + "ffn_norm", D)
        if i < d["dense"]:
            add(p + "ffn_gate", D, d["F"])
            add(p + "ffn_up", D, d["F"])
            add(p + "ffn_down", d["F"], D)
            continue
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
    add("lmd_final_norm", D)
    add("lmd_head", D, d["V"])
    return out


def random_parameters(desc, seed=0, dtype="float32"):
    """Seeded parameters for tests and examples (host arrays; a real
    model's come from its checkpoint): matrices ``N(0, 1/fan_in)``, norm
    scales near 1, the selection bias uniform in +-0.01."""
    rng = np.random.RandomState(seed)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("router_bias"):
            v = rng.uniform(-0.01, 0.01, shape)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("shift"):
            v = 0.1 * rng.standard_normal(shape)
        elif name == "lmd_embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) * shape[-2] ** -0.5
        out[name] = np.asarray(v, "float32").astype(
            np_dtype(dt))
    return out


load_parameters = functools.partial(decoder_programs.load_parameters,
                                    parameter_shapes)


def _family(desc, dtype, tokens_per_dispatch):
    """This family's layers and state (``decoder_programs.DecoderFamily``).
    Beside the frame's fetches: ``chosen`` / ``first_chosen`` (a token's
    experts a layer), ``expert_tokens`` and, under ``index_topk``, the
    positions each ``full`` layer chose: ``selected`` ``[layers, S, topk]``
    int32 of a step; of a prefill whose bucket passes ``index_topk``
    ``first_selected``, each prompt's last row as a mask ``[layers, B, T]``
    int8."""
    nn = fluid.layers
    d = decoder_dims(desc)
    topk = d["topk"]
    full_layers = [i for i, kind in enumerate(d["indexer"])
                   if kind == "full"]

    def state(S, P, ps, npp):
        # what a slot owns: pages of latent rows and, under the same table,
        # of the indexers' narrower keys (a ``full`` layer's); nothing of
        # fixed size
        return {"page_pools": collections.OrderedDict(
            [("lmd_pool_%d" % i, {"shape": (P, ps, d["Wp"]), "dtype": dtype})
             for i in range(d["L"])]
            + [("lmd_ipool_%d" % i, {"shape": (P, ps, d["dI"]),
                                     "dtype": dtype})
               for i in full_layers]), "slot_arrays": {}}

    def blocks(f, x, attend):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, kva, w, cq, nx)`` writes layer ``i``'s rows and
        attends (``w(part)``: the layer's parameter). Returns (x, chosen
        per expert layer, tokens per expert per expert layer)."""
        w = f.w
        chosen, counts = [], []
        for i in range(d["L"]):
            p = "lmd_%d_" % i
            nx = nn.rms_norm(x, w(p + "attn_norm"), d["eps"])
            cq = nn.rms_norm(nn.dense_projection(nx, w(p + "q_a")),
                             w(p + "q_norm"), d["eps"])
            q = nn.dense_projection(cq, w(p + "q_b"))
            kva = nn.dense_projection(nx, w(p + "kv_a"))
            att = attend(i, q, kva, lambda part, p=p: w(p + part), cq, nx)
            x = nn.elementwise_add(
                x, nn.dense_projection(att, w(p + "o")))
            nx = nn.rms_norm(x, w(p + "ffn_norm"), d["eps"])
            if i < d["dense"]:
                ff = nn.gated_ffn(nx, w(p + "ffn_gate"), w(p + "ffn_up"),
                                  w(p + "ffn_down"))
            else:
                shared = ((w(p + "shared_gate"), w(p + "shared_up"),
                           w(p + "shared_down")) if d["shared"] else None)
                ff, ch, cnt = nn.dropless_moe_ffn(
                    nx, w(p + "router"), w(p + "router_bias"),
                    w(p + "experts_gate"), w(p + "experts_up"),
                    w(p + "experts_down"), shared=shared, valid=f.valid,
                    top_k=d["k"], norm_topk=d["norm_topk"],
                    scale=d["scale"], held_first=d["first"])
                chosen.append(ch)
                counts.append(cnt)
            x = nn.elementwise_add(x, ff)
        return nn.rms_norm(x, w("lmd_final_norm"), d["eps"]), chosen, counts

    rope_attrs = dict(heads=d["H"], nope_dim=d["dn"], rope_dim=d["dr"],
                      theta=d["theta"], epsilon=d["eps"],
                      interleave=d["interleave"])

    def indexer(i, w, cq, nx, **where):
        """Layer ``i``'s indexer rows: (queries, key, weights)."""
        return nn.indexer_rows(
            cq, nx, w("idx_q"), w("idx_k"), w("idx_k_norm"),
            w("idx_k_shift"), w("idx_w"), heads=d["J"], rope_dim=d["dr"],
            theta=d["theta"], interleave=d["idx_interleave"], **where)

    def prefill(f, x):
        B, T = f.rows, f.bucket
        masks = []     # the choice of each ``full`` layer so far

        def attend(i, q, kva, w, cq, nx):
            q, row = nn.latent_rope_rows(
                q, kva, w("kv_norm"), period=T, **rope_attrs)
            nn.latent_row_prefill(f.state["lmd_pool_%d" % i], row,
                                  f.page_rows, f.lens)
            if i in full_layers:
                qi, ki, wi = indexer(i, w, cq, nx, period=T)
                nn.latent_row_prefill(f.state["lmd_ipool_%d" % i], ki,
                                      f.page_rows, f.lens)
                if T > topk:
                    masks.append(nn.index_select_prefill(
                        qi, ki, wi, f.lens, prompts=B, top_k=topk))
            if not topk:
                return nn.latent_prefill_attention(
                    q, row, w("kv_b"), prompts=B, nope_dim=d["dn"])
            # no mask in a bucket of at most index_topk rows: every
            # earlier position is chosen
            return nn.sparse_latent_prefill_attention(
                q, row, w("kv_b"), masks[-1] if masks else None, f.lens,
                prompts=B, nope_dim=d["dn"])

        x, chosen, _counts = blocks(f, x, attend)
        # each prompt's LAST row of every choice, for checks
        return x, [("first_chosen", chosen), ("first_selected", lambda: [
            nn.gather(nn.reshape(m, shape=[B * T, T]), f.last_idx)
            for m in masks])]

    def step(f, x):
        selected = []  # the choice of each ``full`` layer so far

        def attend(i, q, kva, w, cq, nx):
            pool = f.state["lmd_pool_%d" % i]
            q, row = nn.latent_rope_rows(
                q, kva, w("kv_norm"), positions=f.pos, **rope_attrs)
            nn.latent_row_write(pool, row, f.table, f.pos)
            if not topk:
                return nn.latent_paged_attention(
                    q, w("kv_b"), pool, f.table, f.lengths,
                    nope_dim=d["dn"])
            if i in full_layers:
                ipool = f.state["lmd_ipool_%d" % i]
                qi, ki, wi = indexer(i, w, cq, nx, positions=f.pos)
                nn.latent_row_write(ipool, ki, f.table, f.pos)
                selected.append(nn.index_select_decode(
                    qi, wi, ipool, f.table, f.lengths, top_k=topk))
            return nn.sparse_latent_paged_attention(
                q, w("kv_b"), pool, f.table, selected[-1],
                nope_dim=d["dn"])

        x, chosen, counts = blocks(f, x, attend)
        return x, [("chosen", chosen), ("expert_tokens", counts),
                   ("selected", selected)]

    return decoder_programs.DecoderFamily(
        "lmd", parameter_shapes(desc, dtype), d["V"], state, prefill,
        step, geometry={
            "row_width": d["W"], "pool_width": d["Wp"],
            "moe_layers": list(range(d["dense"], d["L"])),
            # learned sparse attention: what a query attends at most,
            # and the layers that keep an indexer's narrow pool
            "index_topk": topk, "index_layers": full_layers,
            "index_row_width": d["dI"] if topk else 0,
            # the experts held of those routed among, a token's choices
            "experts": {"held": d["E"], "of": d["Er"], "top_k": d["k"]}})


build_latent_moe_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
