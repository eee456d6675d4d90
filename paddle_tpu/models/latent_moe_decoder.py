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

``build_latent_moe_decoder`` returns the programs a
``serving.decoder_session.DecoderOnlySession`` dispatches:

* ``init``: the zeroed row pools ``lmd_pool_<i>`` ``[pages, page_size,
  pool_width(row)]`` (the row rounded up to whole lanes, 576 -> 640: the
  lanes past the row stay zero) and the per-slot loop state
  ``lmd_tok``/``lmd_pos`` ``[S, 1]``.
* ``prefill[T]``, one per length bucket ``T`` (a multiple of the page
  size): ``prompts_per_dispatch(T)`` prompts a dispatch, under the
  builder's token budget; with ``prefill_rungs`` also one program a RUNG
  of prompt rows under it (1, 2, 4, ...: ``prefill_rungs[T][rows]``), so
  that a dispatch of few prompts walks their rows and not the budget's.
  Feeds ``prompt_ids [B*T]``, ``prompt_len [B]``,
  ``slot_idx [B]`` (``num_slots`` for a row of padding: nothing is
  written for it), ``page_rows [B, pages_per_slot]``, ``last_idx [B]``
  (the flat index of each prompt's last token). Writes the latent rows of
  every layer, samples each prompt's first token from its last position's
  logits and installs ``lmd_tok``/``lmd_pos`` for its slot.
* ``step``: one decode token for every slot; ``Executor.run_multi_step``
  runs ``tokens_per_dispatch`` of them a dispatch. Feeds ``page_table
  [S, pages_per_slot]`` and ``live [S, 1]`` from the host's mirror (a
  slot that is not live has length 0, writes to the trash page and is
  neither routed nor counted), so a cancel or a page grown costs no
  dispatch of its own.

The parameters are declared by name (``parameter_shapes``) and come from a
checkpoint: ``load_parameters`` puts them into the scope; there is no
startup initialiser (a 4 G-parameter model is never made twice).
"""

import collections

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.latent_attention import pool_width
from paddle_tpu.kernels.paged_attention import pages_for

__all__ = ["decoder_dims", "parameter_shapes", "random_parameters",
           "load_parameters", "build_latent_moe_decoder"]


def decoder_dims(desc):
    """The sizes the programs are built from, by the config's keys."""
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
    check_served(desc)
    _sparse_dims(desc, d)
    if d["dn"] + d["dr"] != d["dv"]:
        raise NotImplementedError(
            "prefill runs the flash kernel at one head width: "
            "qk_nope_head_dim + qk_rope_head_dim (%d) must equal "
            "v_head_dim (%d)" % (d["dn"] + d["dr"], d["dv"]))
    d["W"] = d["C"] + d["dr"]          # the cached row
    d["Wp"] = pool_width(d["W"])       # as the pool holds it
    return d


def check_served(desc):
    """Refuse, by the key at fault, a description whose keys ask for what
    this builder does not serve (``decoder_dims`` and the session's
    ``builder_for`` both ask; only the keys that are there are read)."""
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


def load_named(scope, named, shapes=None):
    """Put ``named`` ({name: array}) into ``scope``; with ``shapes``
    ({name: (shape, dtype)}) every parameter must be there with its
    shape. Any decoder builder's ``load_parameters``."""
    for name, (shape, _dt) in (shapes or {}).items():
        if name not in named:
            raise KeyError("the checkpoint has no parameter %r" % name)
        if tuple(named[name].shape) != shape:
            raise ValueError("%s: the model needs %s, the checkpoint has %s"
                             % (name, shape, tuple(named[name].shape)))
    for name, value in named.items():
        scope.var(name).set(value)


def load_parameters(scope, named, desc=None, dtype=None):
    """Put a checkpoint's arrays into ``scope`` under the programs' names.
    With ``desc`` every parameter must be there with its shape."""
    load_named(scope, named, desc and parameter_shapes(desc,
                                                       dtype or "bfloat16"))


def build_latent_moe_decoder(desc, num_slots, max_positions, page_size,
                             prefill_buckets, num_pages=None,
                             prefill_token_budget=2048, sampler=None,
                             dtype="bfloat16", probe_rows=0,
                             tokens_per_dispatch=1, prefill_rungs=False):
    """Build the serving programs (module docstring). Returns a dict:
    ``init``, ``prefill`` ({bucket: program}), ``prefill_rungs`` ({bucket:
    {prompt rows: program}}: the same programs, and with ``prefill_rungs``
    one for every power of two of rows under a bucket's most), ``step``,
    ``fetches`` (the names to fetch: ``token``, ``first_token``,
    ``expert_tokens`` and, for checks, ``logits``, ``first_logits``,
    ``chosen``, ``first_chosen``) and ``geometry`` (slots, pages, buckets
    and prompts a dispatch).

    ``probe_rows`` > 0 gives the step program one more feed,
    ``probe_slots [probe_rows]``, and the fetch ``probe_logits``
    ``[probe_rows, vocab]``: the logits of those slots alone, so that
    whoever compares a served stream's logits with a reference can fetch
    them from the SAME executable it serves with, every dispatch. It is
    not free: the gather makes the step write all slots' logits out
    (158 MB a token step at the published widths) where the program
    without it fuses the sampler's argmax into the head's product.

    ``tokens_per_dispatch`` is every builder's (the session passes it): no
    pool of this model is sized by it."""
    from paddle_tpu import unique_name
    from paddle_tpu.models.transformer import _sampler_attrs

    nn = fluid.layers
    d = decoder_dims(desc)
    S, ps = int(num_slots), int(page_size)
    npp = pages_for(max_positions, ps)
    P = int(num_pages) if num_pages else 1 + S * npp
    samp = _sampler_attrs(sampler)
    buckets = sorted(int(t) for t in prefill_buckets)
    if any(t % ps for t in buckets):
        raise ValueError("every prefill bucket (%s) must be a multiple of "
                         "the page size %d: rows are written a page at a "
                         "time" % (buckets, ps))
    per_dispatch = {t: max(1, int(prefill_token_budget) // t)
                    for t in buckets}
    # the rows a bucket's programs are built for: the most a dispatch
    # takes and, with prefill_rungs, every power of two under it
    rungs = {t: [2 ** j for j in range((most - 1).bit_length())
                 if prefill_rungs] + [most]
             for t, most in per_dispatch.items()}
    shapes = parameter_shapes(desc, dtype)
    moe_layers = list(range(d["dense"], d["L"]))
    topk = d["topk"]
    full_layers = [i for i, kind in enumerate(d["indexer"])
                   if kind == "full"]

    def declare(blk, name, shape, dt):
        return blk.create_var(name=name, shape=list(shape), dtype=dt,
                              persistable=True)

    ipools = {}    # layer -> its narrow pool in the program being built

    def state(blk):
        pools = [declare(blk, "lmd_pool_%d" % i, (P, ps, d["Wp"]), dtype)
                 for i in range(d["L"])]
        # the indexers' keys: a narrow pool a ``full`` layer, same pages
        for i in full_layers:
            ipools[i] = declare(blk, "lmd_ipool_%d" % i, (P, ps, d["dI"]),
                                dtype)
        return (pools, declare(blk, "lmd_tok", (S, 1), "int64"),
                declare(blk, "lmd_pos", (S, 1), "int64"))

    def blocks(blk, x, attend, valid):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, kva, w, cq, nx)`` writes layer ``i``'s rows and
        attends (``w(part)``: the layer's parameter). Returns (x, chosen
        per expert layer, tokens per expert per expert layer)."""
        def w(name):
            return declare(blk, name, *shapes[name])

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
                    w(p + "experts_down"), shared=shared, valid=valid,
                    top_k=d["k"], norm_topk=d["norm_topk"],
                    scale=d["scale"], held_first=d["first"])
                chosen.append(ch)
                counts.append(cnt)
            x = nn.elementwise_add(x, ff)
        return nn.rms_norm(x, w("lmd_final_norm"), d["eps"]), chosen, counts

    def stacked(name, parts, shape, dtype="int32"):
        """The per-layer parts as ONE fetchable variable."""
        out = nn.concat([nn.reshape(c, shape=[1] + shape) for c in parts],
                        axis=0)
        return nn.assign(out, output=fluid.default_main_program()
                         .global_block().create_var(name=name, dtype=dtype))

    rope_attrs = dict(heads=d["H"], nope_dim=d["dn"], rope_dim=d["dr"],
                      theta=d["theta"], epsilon=d["eps"],
                      interleave=d["interleave"])

    def indexer(i, w, cq, nx, **where):
        """Layer ``i``'s indexer rows: (queries, key, weights)."""
        return nn.indexer_rows(
            cq, nx, w("idx_q"), w("idx_k"), w("idx_k_norm"),
            w("idx_k_shift"), w("idx_w"), heads=d["J"], rope_dim=d["dr"],
            theta=d["theta"], interleave=d["idx_interleave"], **where)

    with unique_name.guard({}):
        init = fluid.Program()
        with fluid.program_guard(init, fluid.Program()):
            blk = init.global_block()
            pools, tok, pos = state(blk)
            for pool in pools:
                nn.assign(nn.fill_constant([P, ps, d["Wp"]], dtype, 0.0),
                          output=pool)
            for pool in ipools.values():
                nn.assign(nn.fill_constant([P, ps, d["dI"]], dtype, 0.0),
                          output=pool)
            nn.assign(nn.fill_constant([S, 1], "int64", 0), output=tok)
            nn.assign(nn.fill_constant([S, 1], "int64", 0), output=pos)

        by_rows = {T: {} for T in buckets}
        for T, B in [(T, B) for T in buckets for B in rungs[T]]:
            prog = by_rows[T][B] = fluid.Program()
            with unique_name.guard({}), \
                    fluid.program_guard(prog, fluid.Program()):
                blk = prog.global_block()
                pools, tok, pos = state(blk)

                def feed(name, shape):
                    return nn.data(name, shape=shape, dtype="int64",
                                   append_batch_size=False)

                ids = feed("prompt_ids", [B * T])
                lens = feed("prompt_len", [B])
                slot_idx = feed("slot_idx", [B])
                page_rows = feed("page_rows", [B, npp])
                last_idx = feed("last_idx", [B])
                valid = nn.reshape(
                    nn.sequence_mask(lens, maxlen=T, dtype="int64"),
                    shape=[B * T])
                x = nn.embedding_rows(
                    declare(blk, "lmd_embed", *shapes["lmd_embed"]), ids)

                masks = []     # the choice of each ``full`` layer so far

                def attend(i, q, kva, w, cq, nx, B=B, T=T, pools=pools,
                           page_rows=page_rows, lens=lens, masks=masks):
                    q, row = nn.latent_rope_rows(
                        q, kva, w("kv_norm"), period=T, **rope_attrs)
                    nn.latent_row_prefill(pools[i], row, page_rows, lens)
                    if i in ipools:
                        qi, ki, wi = indexer(i, w, cq, nx, period=T)
                        nn.latent_row_prefill(ipools[i], ki, page_rows, lens)
                        if T > topk:
                            masks.append(nn.index_select_prefill(
                                qi, ki, wi, lens, prompts=B, top_k=topk))
                    if not topk:
                        return nn.latent_prefill_attention(
                            q, row, w("kv_b"), prompts=B, nope_dim=d["dn"])
                    # no mask in a bucket of at most index_topk rows:
                    # every earlier position is chosen
                    return nn.sparse_latent_prefill_attention(
                        q, row, w("kv_b"), masks[-1] if masks else None,
                        lens, prompts=B, nope_dim=d["dn"])

                x, chosen, counts = blocks(blk, x, attend, valid)
                last = nn.gather(x, last_idx)                  # [B, D]
                logits = nn.dense_projection(
                    last, declare(blk, "lmd_head", *shapes["lmd_head"]),
                    out_dtype="float32")
                logits = nn.assign(
                    nn.reshape(logits, shape=[B, 1, d["V"]]),
                    output=blk.create_var(name="lmd_first_logits",
                                          dtype="float32"))
                lens2 = nn.reshape(lens, shape=[B, 1])
                first, _p, _d = nn.slot_decode_sample(
                    logits, lens2, eos_id=0,
                    max_length=int(max_positions) + 2, **samp)
                nn.assign(first, output=blk.create_var(
                    name="lmd_first_tok", dtype="int64"))
                nn.slot_rows_write(tok, slot_idx, first)
                nn.slot_rows_write(pos, slot_idx, lens2)
                if chosen:
                    stacked("lmd_first_chosen", chosen, [B * T, d["k"]])
                if masks:
                    # each prompt's LAST row of every choice, for checks
                    stacked("lmd_first_selected", [
                        nn.gather(nn.reshape(m, shape=[B * T, T]), last_idx)
                        for m in masks], [B, T], dtype="int8")

        step = fluid.Program()
        with unique_name.guard({}), \
                fluid.program_guard(step, fluid.Program()):
            blk = step.global_block()
            pools, tok, pos = state(blk)
            table = nn.data("page_table", shape=[S, npp], dtype="int64",
                            append_batch_size=False)
            live = nn.data("live", shape=[S, 1], dtype="int64",
                           append_batch_size=False)
            # resident rows AFTER this step's write; 0 for an empty slot
            lengths = nn.elementwise_mul(
                nn.increment(pos, value=1, in_place=False), live)
            done = nn.elementwise_sub(
                nn.fill_constant([S, 1], "int64", 1), live)
            x = nn.embedding_rows(
                declare(blk, "lmd_embed", *shapes["lmd_embed"]), tok)

            selected = []  # the choice of each ``full`` layer so far

            def attend(i, q, kva, w, cq, nx):
                q, row = nn.latent_rope_rows(
                    q, kva, w("kv_norm"), positions=pos, **rope_attrs)
                nn.latent_row_write(pools[i], row, table, pos)
                if not topk:
                    return nn.latent_paged_attention(
                        q, w("kv_b"), pools[i], table, lengths,
                        nope_dim=d["dn"])
                if i in ipools:
                    qi, ki, wi = indexer(i, w, cq, nx, positions=pos)
                    nn.latent_row_write(ipools[i], ki, table, pos)
                    selected.append(nn.index_select_decode(
                        qi, wi, ipools[i], table, lengths, top_k=topk))
                return nn.sparse_latent_paged_attention(
                    q, w("kv_b"), pools[i], table, selected[-1],
                    nope_dim=d["dn"])

            x, chosen, counts = blocks(blk, x, attend, live)
            logits = nn.dense_projection(
                x, declare(blk, "lmd_head", *shapes["lmd_head"]),
                out_dtype="float32")
            logits = nn.assign(
                nn.reshape(logits, shape=[S, 1, d["V"]]),
                output=blk.create_var(name="lmd_logits", dtype="float32"))
            if probe_rows:
                probe = nn.data("probe_slots", shape=[int(probe_rows)],
                                dtype="int64", append_batch_size=False)
                nn.assign(
                    nn.gather(nn.reshape(logits, shape=[S, d["V"]]), probe),
                    output=blk.create_var(name="lmd_probe_logits",
                                          dtype="float32"))
            tok_new, pos_new, _done = nn.slot_decode_sample(
                logits, pos, done=done, eos_id=0,
                max_length=int(max_positions) + 2, **samp)
            nn.assign(tok_new, output=blk.create_var(
                name="lmd_step_tok", dtype="int64"))
            if chosen:
                stacked("lmd_chosen", chosen, [S, d["k"]])
                stacked("lmd_expert_tokens", counts, [d["E"]])
            if selected:
                stacked("lmd_selected", selected, [S, topk])
            nn.assign(tok_new, output=tok)
            nn.assign(pos_new, output=pos)

    has_moe = bool(moe_layers)
    return {
        "init": init, "step": step, "prefill_rungs": by_rows,
        "prefill": {T: by_rows[T][per_dispatch[T]] for T in buckets},
        "fetches": {
            "token": "lmd_step_tok", "first_token": "lmd_first_tok",
            "logits": "lmd_logits", "first_logits": "lmd_first_logits",
            "probe_logits": "lmd_probe_logits" if probe_rows else None,
            "expert_tokens": "lmd_expert_tokens" if has_moe else None,
            "chosen": "lmd_chosen" if has_moe else None,
            "first_chosen": "lmd_first_chosen" if has_moe else None,
            # the positions each ``full`` layer chose: [layers, S, topk]
            # int32 of a step; of a prefill whose bucket passes index_topk
            # each prompt's last row as a mask [layers, B, T] int8
            "selected": "lmd_selected" if topk else None,
            "first_selected": "lmd_first_selected" if topk else None},
        "geometry": {
            "num_slots": S, "page_size": ps, "pages_per_slot": npp,
            "num_pages": P, "row_width": d["W"], "pool_width": d["Wp"],
            "buckets": buckets,
            "prompts_per_dispatch": per_dispatch,
            "prefill_rungs": rungs,
            "prefill_token_budget": int(prefill_token_budget),
            "moe_layers": moe_layers, "dtype": dtype,
            # learned sparse attention: what a query attends at most, and
            # the layers that keep an indexer's narrow pool
            "index_topk": topk, "index_layers": full_layers,
            "index_row_width": d["dI"] if topk else 0,
            # the experts held of those routed among, and a token's choices
            "experts": {"held": d["E"], "of": d["Er"], "top_k": d["k"]},
            # what a slot owns: pages of latent rows (and, under the same
            # table, of the indexers' narrower keys), nothing of fixed size
            "state": {"page_pools": collections.OrderedDict(
                [("lmd_pool_%d" % i, {"shape": (P, ps, d["Wp"]),
                                      "dtype": dtype})
                 for i in range(d["L"])]
                + [("lmd_ipool_%d" % i, {"shape": (P, ps, d["dI"]),
                                         "dtype": dtype})
                   for i in full_layers]), "slot_arrays": {}}},
    }
