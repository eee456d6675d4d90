"""Transformer encoder-decoder for machine translation.

Reference parity: the reference's Transformer benchmark model
(``tests/unittests/dist_transformer.py`` / ``benchmark/fluid/models/
machine_translation.py`` attention seq2seq). TPU-first differences:
attention is the fused scaled_dot_product_attention op (Pallas flash on
TPU), sequences are dense-padded [batch, T] with explicit length masks,
and pre-norm residual blocks (better large-scale training stability).
"""

import paddle_tpu as fluid
from paddle_tpu.observability.explain import (
    name_program as _name_program, setup_span as _setup_span)


def _ffn(x, d_model, d_inner, name):
    h = fluid.layers.fc(
        input=x, size=d_inner, num_flatten_dims=2, act="relu",
        name=name + "_fc1",
    )
    return fluid.layers.fc(
        input=h, size=d_model, num_flatten_dims=2, name=name + "_fc2"
    )


def _prenorm(x, name):
    return fluid.layers.layer_norm(
        x, begin_norm_axis=2, name=name + "_ln"
    )


def _residual(x, y, dropout, is_test, name):
    if dropout:
        y = fluid.layers.dropout(y, dropout_prob=dropout, is_test=is_test)
    return fluid.layers.elementwise_add(x, y)


def _self_attention_block(x, mask, n_head, d_model, dropout, is_test, name):
    """Pre-norm self-attention + residual — the shared first half of an
    encoder layer (dense-FFN here, MoE-FFN in switch_transformer)."""
    attn = fluid.layers.multi_head_attention(
        _prenorm(x, name + "_attn"), None, None,
        d_key=d_model // n_head,
        d_value=d_model // n_head,
        d_model=d_model,
        n_head=n_head,
        mask=mask,
        is_test=is_test,
        name=name + "_mha",
    )
    return _residual(x, attn, dropout, is_test, name + "_res1")


def encoder_layer(x, mask, n_head, d_model, d_inner, dropout, is_test, name):
    x = _self_attention_block(x, mask, n_head, d_model, dropout, is_test,
                              name)
    ff = _ffn(_prenorm(x, name + "_ffn"), d_model, d_inner, name + "_ffn")
    return _residual(x, ff, dropout, is_test, name + "_res2")


def decoder_layer(x, enc_out, cross_mask, n_head, d_model,
                  d_inner, dropout, is_test, name):
    self_attn = fluid.layers.multi_head_attention(
        _prenorm(x, name + "_sattn"), None, None,
        d_key=d_model // n_head,
        d_value=d_model // n_head,
        d_model=d_model,
        n_head=n_head,
        causal=True,
        is_test=is_test,
        name=name + "_smha",
    )
    x = _residual(x, self_attn, dropout, is_test, name + "_res1")
    cross = fluid.layers.multi_head_attention(
        _prenorm(x, name + "_cattn"), enc_out, enc_out,
        d_key=d_model // n_head,
        d_value=d_model // n_head,
        d_model=d_model,
        n_head=n_head,
        mask=cross_mask,
        is_test=is_test,
        name=name + "_cmha",
    )
    x = _residual(x, cross, dropout, is_test, name + "_res2")
    ff = _ffn(_prenorm(x, name + "_ffn"), d_model, d_inner, name + "_ffn")
    return _residual(x, ff, dropout, is_test, name + "_res3")


def build(
    src_vocab_size=1000,
    trg_vocab_size=1000,
    max_length=64,
    n_layer=2,
    n_head=4,
    d_model=128,
    d_inner=512,
    dropout=0.1,
    label_smooth_eps=0.1,
    is_test=False,
):
    """Returns (avg_cost, feeds, extras). Feeds: src_word [B,S], src_len
    [B,1], trg_word [B,T] (decoder input), trg_len [B,1], label [B,T]."""
    src = fluid.layers.data("src_word", shape=[max_length], dtype="int64")
    src_len = fluid.layers.data("src_len", shape=[1], dtype="int64")
    trg = fluid.layers.data("trg_word", shape=[max_length], dtype="int64")
    label = fluid.layers.data("label", shape=[max_length], dtype="int64")

    src_mask = fluid.layers.sequence_mask(
        src_len, maxlen=max_length, dtype="float32"
    )  # [B, S] validity

    # Embeddings + sinusoid position encoding
    src_emb = fluid.layers.embedding(
        input=src, size=[src_vocab_size, d_model],
        param_attr=fluid.ParamAttr(name="src_emb"),
    )
    src_emb = fluid.layers.scale(src_emb, scale=d_model ** 0.5)
    enc_in = fluid.layers.add_position_encoding(src_emb)

    trg_emb = fluid.layers.embedding(
        input=trg, size=[trg_vocab_size, d_model],
        param_attr=fluid.ParamAttr(name="trg_emb"),
    )
    trg_emb = fluid.layers.scale(trg_emb, scale=d_model ** 0.5)
    dec_in = fluid.layers.add_position_encoding(trg_emb)

    enc = enc_in
    for i in range(n_layer):
        enc = encoder_layer(
            enc, src_mask, n_head, d_model, d_inner, dropout, is_test,
            "enc_%d" % i,
        )
    enc = _prenorm(enc, "enc_final")

    dec = dec_in
    for i in range(n_layer):
        dec = decoder_layer(
            dec, enc, src_mask, n_head, d_model, d_inner, dropout,
            is_test, "dec_%d" % i,
        )
    dec = _prenorm(dec, "dec_final")

    logits = fluid.layers.fc(
        input=dec, size=trg_vocab_size, num_flatten_dims=2,
        name="proj_logits",
    )

    # Smoothed cross entropy in factored form: with q = eps/V + (1-eps)*onehot,
    #   -sum_i q_i * logp_i = lse - (1-eps) * x_label - (eps/V) * sum_i x_i,
    # algebraically identical to one_hot -> label_smooth -> soft-label CE
    # (the reference benchmark's formulation) but never materializes the
    # [B, T, V] soft-label tensor — at V=32k that tensor costs more HBM
    # traffic than a whole decoder layer — nor anything else of that size
    # but the logits themselves (bf16 under AMP): see ops/loss_ops.py. The
    # one_hot/label_smooth ops remain available (and tested) for programs
    # that want explicit soft labels, e.g. distillation targets.
    flat_logits = fluid.layers.reshape(logits, shape=[-1, trg_vocab_size])
    flat_label = fluid.layers.reshape(label, shape=[-1, 1])
    cost = fluid.layers.fused_label_smooth_ce(
        flat_logits, flat_label, epsilon=label_smooth_eps)

    # Mask loss on padded target positions.
    trg_len = fluid.layers.data("trg_len", shape=[1], dtype="int64")
    trg_mask = fluid.layers.sequence_mask(
        trg_len, maxlen=max_length, dtype="float32"
    )
    cost = fluid.layers.reshape(cost, shape=[-1, max_length])
    masked = fluid.layers.elementwise_mul(cost, trg_mask)
    total = fluid.layers.reduce_sum(masked)
    denom = fluid.layers.reduce_sum(trg_mask)
    avg_cost = fluid.layers.elementwise_div(total, denom)

    feeds = [src, src_len, trg, trg_len, label]
    return avg_cost, feeds, {"logits": logits}


def build_inference(train_prog, logits):
    """Derive the generation graph from the TRAINED program: clone with
    is_test flipped (inference dropout) and prune to the logits fetch —
    the loss head, backward and optimizer ops all fall away, so running
    it cannot touch the weights. Parameters bind through the shared
    scope. Used by greedy_generate/beam_generate below."""
    from paddle_tpu import io

    return io.prune_program(
        train_prog.clone(for_test=True),
        ["src_word", "src_len", "trg_word"],
        [logits.name if hasattr(logits, "name") else logits],
    )


def greedy_generate(exe, infer_prog, logits_var, src, src_len,
                    max_length, bos_id=1, eos_id=2):
    """Greedy decode by re-running the full (fixed-shape) decoder over
    the growing prefix — the whole-program-XLA analog of the reference's
    re-score loop; one executable serves every step because shapes never
    change. Returns [B, max_length] int64 (eos-padded)."""
    import numpy as np

    bs = src.shape[0]
    trg = np.full((bs, max_length), eos_id, np.int64)
    trg[:, 0] = bos_id
    done = np.zeros(bs, bool)
    for t in range(max_length - 1):
        (lg,) = exe.run(
            infer_prog,
            feed={
                "src_word": src,
                "src_len": src_len,
                "trg_word": trg,
            },
            fetch_list=[logits_var],
        )
        nxt = np.asarray(lg)[:, t, :].argmax(-1)
        nxt = np.where(done, eos_id, nxt)
        trg[:, t + 1] = nxt
        done |= nxt == eos_id
        if done.all():
            break
    return trg


def _log_softmax_rows(step):
    """Stable log-softmax over the vocab dim of [N, V] float64 rows."""
    import numpy as np

    mx = step.max(-1, keepdims=True)
    return step - mx - np.log(np.exp(step - mx).sum(-1, keepdims=True))


def _gnmt_penalized_scores(trg_bk, scores, eos_id, len_penalty):
    """GNMT length-penalty division: ``scores / ((5 + len) / 6) ** p``
    over ``[..., K, T]`` hypothesis rows (length = through the first
    eos after bos, or the full budget). float64, broadcast over any
    leading batch dims."""
    import numpy as np

    tail = trg_bk[..., 1:]
    has_eos = (tail == eos_id).any(-1)
    first = (tail == eos_id).argmax(-1)
    lengths = np.where(has_eos, first + 1,
                       trg_bk.shape[-1]).astype(np.float64)
    lp = ((5.0 + lengths) / 6.0) ** float(len_penalty)
    return np.asarray(scores, np.float64) / lp


def _pick_best_beam(trg, pre_scores, bs, K, max_length, eos_id,
                    len_penalty):
    """GNMT length-penalty selection over the final beams."""
    import numpy as np

    trg_bk = trg.reshape(bs, K, max_length)
    best = _gnmt_penalized_scores(
        trg_bk, pre_scores, eos_id, len_penalty).argmax(-1)
    return trg_bk[np.arange(bs), best]


def gnmt_rescore_nbest(tokens, scores, eos_id, len_penalty):
    """Rescore one final beam n-best (``tokens [K, T]`` bos-led rows,
    ``scores [K]`` accumulated log-probs) with the GNMT length penalty
    ``_pick_best_beam`` applies, and reorder score-descending under the
    penalized scores. Returns ``(order [K] int64, tokens[order],
    penalized_scores[order] float32)`` — ``order`` is the permutation of
    the INPUT hypothesis indices, which the wire protocol forwards so a
    streaming client can realign its survivor-chunk replay with the
    rescored ``beam_end``. The sort is stable: ``len_penalty = 0``
    divides by 1 everywhere and returns the identity order."""
    import numpy as np

    tokens = np.asarray(tokens)
    penalized = _gnmt_penalized_scores(tokens, scores, eos_id,
                                       len_penalty)
    order = np.argsort(-penalized, kind="stable").astype(np.int64)
    return order, tokens[order], penalized[order].astype(np.float32)


def beam_generate(exe, infer_prog, logits_var, src, src_len, max_length,
                  beam_size=4, bos_id=1, eos_id=2, len_penalty=0.6):
    """Beam-search decode over the same fixed-shape program: beams ride
    the batch dimension (B*K rows); the per-step selection (incl.
    finished-beam freezing and first-step duplicate suppression) is
    ops/beam_search_ops.beam_step — the same lattice step the in-graph
    beam_search op uses. A GNMT-style length penalty picks the final
    beam. Returns [B, max_length] int64 (best beam per source)."""
    import numpy as np

    from paddle_tpu.ops.beam_search_ops import beam_step

    bs = src.shape[0]
    K = int(beam_size)
    src_k = np.repeat(src, K, axis=0)
    len_k = np.repeat(src_len, K, axis=0)
    trg = np.full((bs * K, max_length), eos_id, np.int64)
    trg[:, 0] = bos_id
    # int32: beam_step mirrors the dtype, and jnp int64 would
    # warn-and-truncate with x64 disabled
    pre_ids = np.full((bs, K), bos_id, np.int32)
    pre_scores = np.full((bs, K), -1e9, np.float32)
    pre_scores[:, 0] = 0.0  # only beam 0 live at t=0 (no K duplicates)
    rows = np.arange(bs)[:, None]
    for t in range(max_length - 1):
        (lg,) = exe.run(
            infer_prog,
            feed={
                "src_word": src_k,
                "src_len": len_k,
                "trg_word": trg,
            },
            fetch_list=[logits_var],
        )
        step = _log_softmax_rows(
            np.asarray(lg)[:, t, :].astype(np.float64))  # [B*K, V]
        token, sel_scores, parent = beam_step(
            pre_ids, pre_scores, step.reshape(
                bs, K, -1).astype(np.float32), eos_id)
        token = np.asarray(token)
        parent = np.asarray(parent)
        # prefixes follow their beams (the decoder re-reads them)
        trg_bk = trg.reshape(bs, K, max_length)[rows, parent]
        trg_bk[:, :, t + 1] = token
        trg = trg_bk.reshape(bs * K, max_length)
        pre_ids = token
        pre_scores = np.asarray(sel_scores)
        if (token == eos_id).all():
            break
    return _pick_best_beam(trg, pre_scores, bs, K, max_length, eos_id,
                           len_penalty)


def position_encoding_row(t, d_model, dtype="float32"):
    """Host mirror of the add_position_encoding table's row ``t`` —
    fed to the cached decode step (exact same formula as
    ops/attention_ops.py _lower_position_encoding)."""
    import numpy as np

    i = np.arange(d_model // 2, dtype=np.float64)
    angle = float(t) / np.power(10000.0, 2.0 * i / d_model)
    return np.concatenate([np.sin(angle), np.cos(angle)]).astype(
        dtype)[None, :]


def position_encoding_table(max_length, d_model, dtype="float32"):
    """The full [max_length, d_model] sinusoid table, row-exact with
    ``position_encoding_row`` — fed once to the paged decoder's init
    program (and usable anywhere a whole-table mirror is needed)."""
    import numpy as np

    return np.concatenate(
        [position_encoding_row(t, d_model, dtype=dtype)
         for t in range(int(max_length))], axis=0)


def build_cached_decoder(
    batch_size,
    src_vocab_size=1000,
    trg_vocab_size=1000,
    max_length=64,
    n_layer=2,
    n_head=4,
    d_model=128,
    d_inner=512,
):
    """Incremental (KV-cached) decoding: O(T) attention per new token
    instead of re-running the decoder over the whole prefix.

    Returns (prepare_prog, step_prog, logits_name). ``prepare_prog``
    runs once per batch: encoder forward, per-layer cross K/V
    projections, src mask, and zeroed self-attention caches — all
    written to persistable scope vars. ``step_prog`` consumes one token
    per run, updates the K/V caches in place via dynamic_update_slice
    (the optimizer-style persistable-state convention), and fetches
    [B, 1, V] logits.

    Build it under the same fresh ``unique_name`` scope as the training
    ``build()`` (both start from empty counters, and every
    param-creating layer here carries the training build's explicit
    name), so parameters bind through the shared scope.
    """
    from paddle_tpu import unique_name

    nn = fluid.layers
    B, T, D = int(batch_size), int(max_length), int(d_model)
    dh = D // n_head

    def heads(x):
        # [B, seq, H*dh] -> [B, H, seq, dh] (seq inferred by reshape)
        return nn.transpose(
            nn.reshape(x, shape=[0, 0, n_head, dh]), perm=[0, 2, 1, 3])

    with unique_name.guard({}):
        prepare = fluid.Program()
        prep_startup = fluid.Program()
        with fluid.program_guard(prepare, prep_startup):
            src = nn.data("src_word", shape=[T], dtype="int64")
            src_len = nn.data("src_len", shape=[1], dtype="int64")
            src_mask = nn.sequence_mask(src_len, maxlen=T, dtype="float32")
            emb = nn.embedding(
                input=src, size=[src_vocab_size, D],
                param_attr=fluid.ParamAttr(name="src_emb"))
            enc = nn.add_position_encoding(nn.scale(emb, scale=D ** 0.5))
            for i in range(n_layer):
                enc = encoder_layer(enc, src_mask, n_head, D, d_inner,
                                    0.0, True, "enc_%d" % i)
            enc = _prenorm(enc, "enc_final")
            blk = prepare.global_block()

            def persist(name, value):
                out = blk.create_var(name=name, shape=None,
                                     dtype="float32", persistable=True)
                nn.assign(value, output=out)

            persist("gen_src_mask", src_mask)
            for i in range(n_layer):
                kc = heads(nn.fc(enc, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name="dec_%d_cmha_k" % i))
                vc = heads(nn.fc(enc, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name="dec_%d_cmha_v" % i))
                persist("gen_kcross_%d" % i, kc)
                persist("gen_vcross_%d" % i, vc)
                zeros = nn.fill_constant([B, n_head, T, dh], "float32",
                                         0.0)
                persist("gen_kcache_%d" % i, zeros)
                persist("gen_vcache_%d" % i, zeros)

        step = fluid.Program()
        step_startup = fluid.Program()
        with fluid.program_guard(step, step_startup):
            blk = step.global_block()
            cur = nn.data("cur_tok", shape=[1], dtype="int64")
            pe_row = nn.data("pe_row", shape=[1, D], dtype="float32")
            pos = nn.data("gen_pos", shape=[1], dtype="int64",
                          append_batch_size=False)
            # cache validity is derived from gen_pos in-graph (positions
            # <= pos), so callers cannot feed an inconsistent length
            cache_mask = nn.expand(
                nn.sequence_mask(
                    fluid.layers.increment(pos, value=1, in_place=False),
                    maxlen=T, dtype="float32"),
                expand_times=[B, 1])

            def pvar(name, shape):
                return blk.create_var(name=name, shape=shape,
                                      dtype="float32", persistable=True)

            src_mask = pvar("gen_src_mask", [B, T])
            emb = nn.embedding(
                input=cur, size=[trg_vocab_size, D],
                param_attr=fluid.ParamAttr(name="trg_emb"))
            # lookup_table squeezes the trailing singleton id dim
            # ([B, 1] ids -> [B, D]); restore the length-1 seq axis
            emb = nn.reshape(emb, shape=[0, 1, D])
            h = nn.elementwise_add(nn.scale(emb, scale=D ** 0.5), pe_row)
            for i in range(n_layer):
                name = "dec_%d" % i
                kcache = pvar("gen_kcache_%d" % i, [B, n_head, T, dh])
                vcache = pvar("gen_vcache_%d" % i, [B, n_head, T, dh])
                nx = _prenorm(h, name + "_sattn")
                q = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                bias_attr=False, name=name + "_smha_q"))
                k1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_k"))
                v1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_v"))
                kcache = nn.dynamic_update_slice(kcache, k1, pos, axis=2,
                                                 out=kcache)
                vcache = nn.dynamic_update_slice(vcache, v1, pos, axis=2,
                                                 out=vcache)
                att = fluid.layers.scaled_dot_product_attention(
                    q, kcache, vcache, mask=cache_mask,
                    sm_scale=dh ** -0.5)
                att = nn.reshape(nn.transpose(att, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    att, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_smha_o"))
                nx2 = _prenorm(h, name + "_cattn")
                q2 = heads(nn.fc(nx2, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name=name + "_cmha_q"))
                ctx = fluid.layers.scaled_dot_product_attention(
                    q2, pvar("gen_kcross_%d" % i, [B, n_head, T, dh]),
                    pvar("gen_vcross_%d" % i, [B, n_head, T, dh]),
                    mask=src_mask, sm_scale=dh ** -0.5)
                ctx = nn.reshape(nn.transpose(ctx, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    ctx, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_cmha_o"))
                ff = _ffn(_prenorm(h, name + "_ffn"), D, d_inner,
                          name + "_ffn")
                h = nn.elementwise_add(h, ff)
            h = _prenorm(h, "dec_final")
            logits = nn.fc(h, trg_vocab_size, num_flatten_dims=2,
                           name="proj_logits")
    return prepare, step, logits.name


def cached_greedy_generate(exe, prepare_prog, step_prog, logits_name,
                           src, src_len, max_length, d_model,
                           bos_id=1, eos_id=2):
    """Greedy decode through the KV-cached step program: prepare once
    (encoder + cross caches), then one [B, 1] token per step. Matches
    greedy_generate output; cost per step is O(T) attention instead of
    a full-prefix decoder re-run."""
    import numpy as np

    bs = src.shape[0]
    exe.run(prepare_prog, feed={"src_word": src, "src_len": src_len},
            fetch_list=[])
    trg = np.full((bs, max_length), eos_id, np.int64)
    trg[:, 0] = bos_id
    done = np.zeros(bs, bool)
    for t in range(max_length - 1):
        (lg,) = exe.run(
            step_prog,
            feed={
                "cur_tok": trg[:, t:t + 1],
                "pe_row": np.tile(
                    position_encoding_row(t, d_model)[None], (bs, 1, 1)),
                "gen_pos": np.asarray([t], np.int64),
            },
            fetch_list=[logits_name],
        )
        nxt = np.asarray(lg)[:, 0, :].argmax(-1)
        nxt = np.where(done, eos_id, nxt)
        trg[:, t + 1] = nxt
        done |= nxt == eos_id
        if done.all():
            break
    return trg


def build_cache_reorder(batch_size, max_length, n_layer, n_head, d_model):
    """Companion to build_cached_decoder for beam search: permute every
    self-attention cache's batch rows by a fed index vector (beam
    survivors adopt their parent's cache). Cross caches and masks are
    row-constant across a source's beams, so only the self caches move."""
    nn = fluid.layers
    B, T = int(batch_size), int(max_length)
    dh = d_model // n_head
    prog = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(prog, startup):
        blk = prog.global_block()
        parent = nn.reshape(
            nn.data("beam_parent_rows", shape=[1], dtype="int64"),
            shape=[-1])  # [B, 1] feed -> flat row indices
        for i in range(n_layer):
            for kind in ("k", "v"):
                cache = blk.create_var(
                    name="gen_%scache_%d" % (kind, i),
                    shape=[B, n_head, T, dh], dtype="float32",
                    persistable=True)
                nn.assign(nn.gather(cache, parent), output=cache)
    return prog


def cached_beam_generate(exe, prepare_prog, step_prog, reorder_prog,
                         logits_name, src, src_len, max_length, d_model,
                         beam_size=4, bos_id=1, eos_id=2,
                         len_penalty=0.6):
    """Beam search over the KV-cached step program: beams ride the batch
    dim (B*K rows, so build the cached decoder with
    batch_size=B*beam_size), the per-step selection is
    ops/beam_search_ops.beam_step, and surviving beams adopt their
    parent's caches through the reorder program."""
    import numpy as np

    from paddle_tpu.ops.beam_search_ops import beam_step

    bs = src.shape[0]
    K = int(beam_size)
    src_k = np.repeat(src, K, axis=0)
    len_k = np.repeat(src_len, K, axis=0)
    exe.run(prepare_prog, feed={"src_word": src_k, "src_len": len_k},
            fetch_list=[])
    trg = np.full((bs * K, max_length), eos_id, np.int64)
    trg[:, 0] = bos_id
    pre_ids = np.full((bs, K), bos_id, np.int32)
    pre_scores = np.full((bs, K), -1e9, np.float32)
    pre_scores[:, 0] = 0.0
    rows = np.arange(bs)[:, None]
    for t in range(max_length - 1):
        (lg,) = exe.run(
            step_prog,
            feed={
                "cur_tok": trg[:, t:t + 1],
                "pe_row": np.tile(
                    position_encoding_row(t, d_model)[None],
                    (bs * K, 1, 1)),
                "gen_pos": np.asarray([t], np.int64),
            },
            fetch_list=[logits_name],
        )
        step = _log_softmax_rows(
            np.asarray(lg)[:, 0, :].astype(np.float64))
        token, sel_scores, parent = beam_step(
            pre_ids, pre_scores,
            step.reshape(bs, K, -1).astype(np.float32), eos_id)
        token = np.asarray(token)
        parent = np.asarray(parent)
        global_rows = (rows * K + parent).reshape(-1).astype(np.int64)
        exe.run(reorder_prog, feed={
            "beam_parent_rows": global_rows[:, None]}, fetch_list=[])
        trg_bk = trg.reshape(bs, K, max_length)[rows, parent]
        trg_bk[:, :, t + 1] = token
        trg = trg_bk.reshape(bs * K, max_length)
        pre_ids = token
        pre_scores = np.asarray(sel_scores)
        if (token == eos_id).all():
            break
    return _pick_best_beam(trg, pre_scores, bs, K, max_length, eos_id,
                           len_penalty)


def _sampler_attrs(sampler):
    """Normalize a sampler spec (None, dict, or an object with
    strategy/temperature/top_k/seed attributes — serving.Sampler) into
    the slot_decode_sample op's attrs."""
    if sampler is None:
        return {"strategy": "greedy", "temperature": 1.0, "top_k": 0,
                "base_seed": 0}
    if isinstance(sampler, dict):
        src = dict(sampler)
    else:
        src = {"strategy": getattr(sampler, "strategy", "greedy"),
               "temperature": getattr(sampler, "temperature", 1.0),
               "top_k": getattr(sampler, "top_k", 0),
               "base_seed": getattr(sampler, "seed",
                                    getattr(sampler, "base_seed", 0))}
    strategy = src.get("strategy", "greedy")
    if strategy not in ("greedy", "temperature", "top_k"):
        raise ValueError(
            "sampler strategy must be greedy/temperature/top_k, got %r"
            % (strategy,))
    if strategy == "top_k" and int(src.get("top_k", 0)) < 1:
        raise ValueError(
            "sampler strategy 'top_k' needs top_k >= 1 — 0 would "
            "silently sample the full vocabulary")
    return {"strategy": strategy,
            "temperature": float(src.get("temperature", 1.0)),
            "top_k": int(src.get("top_k", 0)),
            "base_seed": int(src.get("base_seed", src.get("seed", 0)))}


def build_slot_decoder(
    num_slots,
    src_vocab_size=1000,
    trg_vocab_size=1000,
    max_length=64,
    n_layer=2,
    n_head=4,
    d_model=128,
    d_inner=512,
    eos_id=2,
    sampler=None,
):
    """Continuous-batching decode: the KV caches become a SLOT-PAGED
    pool (dim 0 = slot, one in-flight sequence per slot) so admissions
    and completions happen mid-flight while ONE fixed-shape step
    executable advances every active sequence — the ragged-paged-
    attention serving shape, built from this op set.

    Returns ``(init_prog, admit_prog, step_prog, token_name)``:

    * ``init_prog`` (run once): allocates the zeroed cache pools —
      per-layer self K/V ``[num_slots, H, T, dh]``, cross K/V pools,
      and the per-slot source mask ``[num_slots, T]`` (column 0 seeded
      valid so an unoccupied slot's cross-attention row is never fully
      masked — softmax over an all-masked row is NaN bait).
    * ``admit_prog`` (once per admitted sequence): encoder forward for
      ONE sequence (feeds ``src_word [1, T]``, ``src_len [1, 1]``,
      ``slot_idx [1]``), then scatters its cross K/V + mask into the
      slot's pool rows and zeroes the slot's self caches — all via
      ``dynamic_update_slice`` along the slot axis. Fixed shapes, so
      every admission reuses one executable.
    * ``step_prog`` (per token): feeds ``cur_tok [S, 1]``,
      ``pe_row [S, 1, D]``, ``gen_pos [S, 1]`` — PER-SLOT positions,
      unlike ``build_cached_decoder``'s single shared position. Each
      slot's new K/V row lands at ITS position via a one-hot
      select-and-add (bit-exact: written positions get exactly the new
      row, others keep exactly the old bits), and each slot's
      attention validity mask derives from its own position in-graph.
      Token selection (``sampler``: greedy default, or a
      temperature/top-k spec with per-slot PRNG streams keyed on
      ``(base_seed, slot, position)``) runs ON DEVICE — the fetch is
      the ``[S, 1]`` int token ids, never the ``[S, 1, V]`` logits, so
      the host round trip per token is vocab-independent.

    Rows are independent end to end (attention, norms and projections
    are per-slot), so a sequence's tokens do not depend on which other
    slots are live — the parity contract tests/test_serving.py pins
    against the dedicated-batch decoders. Build it under the same
    fresh ``unique_name`` scope as the training ``build()``; parameters
    bind through the shared scope by name. Host-side slot management
    lives in ``serving.generation.SlotDecodeSession``.
    """
    from paddle_tpu import unique_name

    nn = fluid.layers
    S, T, D = int(num_slots), int(max_length), int(d_model)
    dh = D // n_head

    def heads(x):
        return nn.transpose(
            nn.reshape(x, shape=[0, 0, n_head, dh]), perm=[0, 2, 1, 3])

    with unique_name.guard({}):
        init = fluid.Program()
        init_startup = fluid.Program()
        with _setup_span("init", init), \
                fluid.program_guard(init, init_startup):
            blk = init.global_block()

            def persist(name, value):
                out = blk.create_var(name=name, shape=None,
                                     dtype="float32", persistable=True)
                nn.assign(value, output=out)

            mask0 = nn.fill_constant([S, T], "float32", 0.0)
            mask0 = nn.dynamic_update_slice(
                mask0, nn.fill_constant([S, 1], "float32", 1.0),
                nn.fill_constant([1], "int64", 0), axis=1)
            persist("gen_src_mask", mask0)
            for i in range(n_layer):
                for kind in ("kcross", "vcross", "kcache", "vcache"):
                    persist("gen_%s_%d" % (kind, i),
                            nn.fill_constant([S, n_head, T, dh],
                                             "float32", 0.0))

        admit = fluid.Program()
        admit_startup = fluid.Program()
        with _setup_span("admit/1", admit), \
                fluid.program_guard(admit, admit_startup):
            blk = admit.global_block()
            src = nn.data("src_word", shape=[T], dtype="int64")
            src_len = nn.data("src_len", shape=[1], dtype="int64")
            slot = nn.data("slot_idx", shape=[1], dtype="int64",
                           append_batch_size=False)
            src_mask = nn.sequence_mask(src_len, maxlen=T,
                                        dtype="float32")  # [1, T]
            emb = nn.embedding(
                input=src, size=[src_vocab_size, D],
                param_attr=fluid.ParamAttr(name="src_emb"))
            enc = nn.add_position_encoding(nn.scale(emb, scale=D ** 0.5))
            for i in range(n_layer):
                enc = encoder_layer(enc, src_mask, n_head, D, d_inner,
                                    0.0, True, "enc_%d" % i)
            enc = _prenorm(enc, "enc_final")

            def pool(name):
                return blk.create_var(name=name,
                                      shape=[S, n_head, T, dh],
                                      dtype="float32", persistable=True)

            mask_pool = blk.create_var(name="gen_src_mask", shape=[S, T],
                                       dtype="float32", persistable=True)
            nn.dynamic_update_slice(mask_pool, src_mask, slot, axis=0,
                                    out=mask_pool)
            zeros_row = nn.fill_constant([1, n_head, T, dh], "float32",
                                         0.0)
            for i in range(n_layer):
                kc = heads(nn.fc(enc, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name="dec_%d_cmha_k" % i))
                vc = heads(nn.fc(enc, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name="dec_%d_cmha_v" % i))
                for pname, row in (("gen_kcross_%d" % i, kc),
                                   ("gen_vcross_%d" % i, vc),
                                   ("gen_kcache_%d" % i, zeros_row),
                                   ("gen_vcache_%d" % i, zeros_row)):
                    p = pool(pname)
                    nn.dynamic_update_slice(p, row, slot, axis=0, out=p)

        step = fluid.Program()
        step_startup = fluid.Program()
        with _setup_span("step", step), \
                fluid.program_guard(step, step_startup):
            blk = step.global_block()
            cur = nn.data("cur_tok", shape=[1], dtype="int64")
            pe_row = nn.data("pe_row", shape=[1, D], dtype="float32")
            pos = nn.data("gen_pos", shape=[1], dtype="int64")  # [S, 1]
            # per-slot validity: positions <= this slot's own pos
            cache_mask = nn.sequence_mask(
                fluid.layers.increment(pos, value=1, in_place=False),
                maxlen=T, dtype="float32")  # [S, T]
            # one-hot of each slot's write position, shaped to select
            # along the cache's T axis: [S, 1, T, 1]
            write_sel = nn.reshape(nn.one_hot(pos, depth=T),
                                   shape=[-1, 1, T, 1])
            keep_sel = nn.scale(write_sel, scale=-1.0, bias=1.0)

            def pvar(name, shape):
                return blk.create_var(name=name, shape=shape,
                                      dtype="float32", persistable=True)

            src_mask = pvar("gen_src_mask", [S, T])
            emb = nn.embedding(
                input=cur, size=[trg_vocab_size, D],
                param_attr=fluid.ParamAttr(name="trg_emb"))
            emb = nn.reshape(emb, shape=[0, 1, D])
            h = nn.elementwise_add(nn.scale(emb, scale=D ** 0.5), pe_row)
            for i in range(n_layer):
                name = "dec_%d" % i
                kcache = pvar("gen_kcache_%d" % i, [S, n_head, T, dh])
                vcache = pvar("gen_vcache_%d" % i, [S, n_head, T, dh])
                nx = _prenorm(h, name + "_sattn")
                q = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                bias_attr=False, name=name + "_smha_q"))
                k1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_k"))
                v1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_v"))
                # per-slot scatter: row i writes at ITS gen_pos[i]; the
                # select-and-add keeps untouched positions bit-identical
                knew = nn.elementwise_add(
                    nn.elementwise_mul(kcache, keep_sel),
                    nn.elementwise_mul(k1, write_sel))
                vnew = nn.elementwise_add(
                    nn.elementwise_mul(vcache, keep_sel),
                    nn.elementwise_mul(v1, write_sel))
                nn.assign(knew, output=kcache)
                nn.assign(vnew, output=vcache)
                att = fluid.layers.scaled_dot_product_attention(
                    q, knew, vnew, mask=cache_mask, sm_scale=dh ** -0.5)
                att = nn.reshape(nn.transpose(att, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    att, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_smha_o"))
                nx2 = _prenorm(h, name + "_cattn")
                q2 = heads(nn.fc(nx2, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name=name + "_cmha_q"))
                ctx = fluid.layers.scaled_dot_product_attention(
                    q2, pvar("gen_kcross_%d" % i, [S, n_head, T, dh]),
                    pvar("gen_vcross_%d" % i, [S, n_head, T, dh]),
                    mask=src_mask, sm_scale=dh ** -0.5)
                ctx = nn.reshape(nn.transpose(ctx, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    ctx, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_cmha_o"))
                ff = _ffn(_prenorm(h, name + "_ffn"), D, d_inner,
                          name + "_ffn")
                h = nn.elementwise_add(h, ff)
            h = _prenorm(h, "dec_final")
            logits = nn.fc(h, trg_vocab_size, num_flatten_dims=2,
                           name="proj_logits")
            tok, _, _ = fluid.layers.slot_decode_sample(
                logits, pos, eos_id=eos_id, max_length=T,
                **_sampler_attrs(sampler))
    return init, admit, step, tok.name


def _slot_state_feeds(npp, beam, rows=1):
    """The feeds admit/join share for a member's registration, ``rows``
    members a call."""
    nn = fluid.layers
    slot = nn.data("slot_idx", shape=[rows], dtype="int64",
                   append_batch_size=False)
    gidx = nn.data("group_idx", shape=[rows], dtype="int64",
                   append_batch_size=False)
    page_row = nn.data("page_row", shape=[npp], dtype="int64")
    start_tok = nn.data("start_tok", shape=[1], dtype="int64")
    start_pos = nn.data("start_pos", shape=[1], dtype="int64")
    if not beam:
        return slot, gidx, page_row, start_tok, start_pos
    # the lane's accumulated log-prob seed: 0 for hypothesis 0,
    # -1e9 for the rest (first-step duplicate suppression)
    start_score = nn.data("start_score", shape=[1], dtype="float32")
    return slot, gidx, page_row, start_tok, start_pos, start_score


def _write_rows(blk, name, shape, value, index, rows, dtype="float32"):
    """Write ``value``'s ``rows`` leading rows into the persistable
    ``name`` at ``index`` along axis 0, in place: one dynamic-update-slice
    for one row, one row scatter for several (an index past the end is
    DROPPED, which is how a batch's rows of padding write nothing)."""
    nn = fluid.layers
    p = blk.create_var(name=name, shape=shape, dtype=dtype,
                       persistable=True)
    if rows == 1:
        nn.dynamic_update_slice(p, value, index, axis=0, out=p)
    else:
        nn.scatter(p, index, value, out=p)


def _register_member(blk, S, npp, feeds, rows=1):
    """Install ``rows`` slots' group id, table row and loop state."""
    nn = fluid.layers
    slot, gidx, page_row, start_tok, start_pos = feeds[:5]

    def srow(name, value, dtype="int64"):
        _write_rows(blk, name, [S, npp] if name == "pgd_table" else [S, 1],
                    value, slot, rows, dtype)

    srow("pgd_group_of", nn.reshape(gidx, shape=[rows, 1]))
    srow("pgd_table", page_row)
    srow("pgd_tok", start_tok)
    srow("pgd_pos", start_pos)
    srow("pgd_done", nn.fill_constant([rows, 1], "int64", 0))
    if len(feeds) > 5:
        srow("pgd_score", feeds[5], "float32")


def _live_row(done, ptable, S):
    """[S, 1] int64, 1 where a slot holds a stream: not ``done`` AND its
    table row is not the trash row. A release (finish, cancel, rollback)
    points the whole row at page 0 and leaves ``pgd_done`` as it was,
    and a slot that holds a stream owns its first page from admission
    on (page 0 never circulates), so the first entry tells the two
    apart from state the step program already holds."""
    nn = fluid.layers
    one = nn.fill_constant([S, 1], "int64", 1)
    holds_pages = nn.elementwise_min(
        nn.slice(ptable, axes=[1], starts=[0], ends=[1]), one)
    return nn.elementwise_mul(nn.elementwise_sub(one, done), holds_pages)


def _build_admit_prog(rows, S, T, D, G, npp, n_layer, n_head, d_inner,
                      src_vocab_size, beam=False):
    """The paged decoder's admission program for ``rows`` sources a
    call: ONE encoder forward over ``[rows, T]`` (each row padded to
    ``T`` and masked by its own length, so a row's arithmetic does not
    depend on its batch mates), each layer's cross K/V and the source
    mask written into the rows' groups, the rows' slots registered.
    ``rows=1`` is the program ``build_paged_slot_decoder`` has always
    returned (one dynamic-update-slice a write); ``rows > 1`` scatters,
    and a row whose ``group_idx`` / ``slot_idx`` lie past the pools' ends
    is padding that writes nothing. Built under the caller's
    ``unique_name`` scope."""
    nn = fluid.layers
    dh = D // n_head
    admit = fluid.Program()
    with fluid.program_guard(admit, fluid.Program()):
        blk = admit.global_block()
        src = nn.data("src_word", shape=[T], dtype="int64")
        src_len = nn.data("src_len", shape=[1], dtype="int64")
        member_feeds = _slot_state_feeds(npp, beam, rows)
        gidx = member_feeds[1]
        src_mask = nn.sequence_mask(src_len, maxlen=T,
                                    dtype="float32")  # [rows, T]
        emb = nn.embedding(
            input=src, size=[src_vocab_size, D],
            param_attr=fluid.ParamAttr(name="src_emb"))
        enc = nn.add_position_encoding(nn.scale(emb, scale=D ** 0.5))
        for i in range(n_layer):
            enc = encoder_layer(enc, src_mask, n_head, D, d_inner,
                                0.0, True, "enc_%d" % i)
        enc = _prenorm(enc, "enc_final")

        def heads(x):
            return nn.transpose(
                nn.reshape(x, shape=[0, 0, n_head, dh]), perm=[0, 2, 1, 3])

        _write_rows(blk, "pgd_src_mask", [G, T], src_mask, gidx, rows)
        for i in range(n_layer):
            kc = heads(nn.fc(enc, dh * n_head, num_flatten_dims=2,
                             bias_attr=False,
                             name="dec_%d_cmha_k" % i))
            vc = heads(nn.fc(enc, dh * n_head, num_flatten_dims=2,
                             bias_attr=False,
                             name="dec_%d_cmha_v" % i))
            _write_rows(blk, "pgd_kcross_%d" % i, [G, n_head, T, dh], kc,
                        gidx, rows)
            _write_rows(blk, "pgd_vcross_%d" % i, [G, n_head, T, dh], vc,
                        gidx, rows)
        _register_member(blk, S, npp, member_feeds, rows)
    return admit


def build_paged_slot_decoder(
    num_slots,
    src_vocab_size=1000,
    trg_vocab_size=1000,
    max_length=64,
    n_layer=2,
    n_head=4,
    d_model=128,
    d_inner=512,
    page_size=8,
    num_pages=None,
    num_groups=None,
    bos_id=1,
    eos_id=2,
    sampler=None,
    beam_width=1,
    speculative=0,
):
    """Block-paged continuous-batching decode: the slot pool's dense
    per-slot self caches (``[S, H, T, dh]``) become a PAGE POOL —
    fixed-size KV pages ``[num_pages, page_size, H * dh]`` (a token's
    row of all heads contiguous: the one layout the chip stores, the
    row scatter writes and the kernel reads without a copy) shared by
    every slot through a per-slot page-index table — and the step
    program becomes a SELF-CONTAINED loop body (token selection,
    position advance and the next token's embedding input all live on
    device), so ``Executor.run_multi_step(step_prog, steps=K)``
    dispatches K decode tokens per host round trip and fetches
    ``[K, S, 1]`` int ids instead of per-token ``[S, 1, V]`` logits.

    Cross-request KV reuse (PR 12): cross-attention K/V is pooled per
    GROUP — ``[num_groups, H, T, dh]`` rows plus a per-slot
    ``group_of`` index — so N slots decoding sampled continuations of
    one source (``SlotDecodeSession.admit_group``) run ONE encoder
    forward and cost one group's cross HBM instead of N dense rows.
    Self-KV pages are refcount-shared host-side; the programs below
    give the host the on-device levers (join a group without an
    encoder run, chunked-prefill a forced prefix, copy-on-write a
    shared page).

    Returns ``(init_prog, admit_prog, join_prog, prefill_prog,
    table_prog, step_prog, token_name)``:

    * ``init_prog`` (once; feeds ``pe_table [T, D]`` — the host's exact
      ``position_encoding_row`` table, so in-graph rows are bit-equal
      to the dense session's fed rows): allocates the zeroed page
      pools, the GROUP cross K/V pools ``[G, H, T, dh]``, the
      per-group source mask (column 0 seeded valid), ``group_of [S,1]``
      (all slots -> group 0), the page table (all rows -> the reserved
      TRASH page 0, where unoccupied slots' writes land harmlessly),
      and the per-slot loop state ``pgd_tok``/``pgd_pos``/``pgd_done``.
    * ``admit_prog`` (once per admitted SOURCE; feeds ``src_word``,
      ``src_len``, ``slot_idx``, ``group_idx``,
      ``page_row [1, pages_per_slot]`` — the host allocator's page ids
      for this slot, unprovisioned tail entries aliasing the last
      valid page — and ``start_tok``/``start_pos [1, 1]``, bos/0
      without a forced prefix): encoder forward for ONE sequence,
      cross K/V + mask scattered into the GROUP's rows, the slot's
      group id, page-table row and loop state installed
      (tok=start_tok, pos=start_pos, done=0). The self pages are NOT
      zeroed — every position a slot attends over was written by that
      slot (or its fork parent) first, so stale page bits are never
      read. Several queued sources at once go through
      :func:`build_admit_batch_prog`, the same program with a rung's
      rows a feed (the session builds one per rung of its ladder).
    * ``join_prog`` (per extra group member; feeds ``slot_idx``,
      ``group_idx``, ``page_row``, ``start_tok``, ``start_pos``):
      registers another slot onto an EXISTING group — no encoder
      forward, no cross write; just group id, table row and loop
      state. This is the fork: the member's table row references the
      parent's pages until copy-on-write splits them.
    * ``prefill_prog`` (per uncached forced prefix; feeds
      ``prefix_word [1, T]``, ``prefix_len``, ``write_from [1, 1]``,
      ``slot_idx``, ``group_idx``): ONE causal decoder forward over
      the whole prefix, cross-attending the group's rows, with each
      layer's K/V scattered into the slot's pages by
      ``paged_kv_prefill`` — only positions in
      ``[write_from, prefix_len - 1)`` are written (a prefix-cache hit
      sets ``write_from`` past the cached pages; pad positions route
      to the trash page), replacing token-by-token prefix stepping
      with one dispatch.
    * copy-on-write dispatches are NOT built here: a fork's first
      write to a shared page runs :func:`build_cow_batch_prog` (the
      bucket-laddered batch program the session builds per rung —
      copies land before any repoint, so shared and prefix-cached page
      bits are immutable; one executable covers a whole step window's
      pairs).
    * ``step_prog`` (K per dispatch, NO feeds): O(page)
      ``paged_kv_write`` at each slot's own position, ragged
      ``paged_attention`` bounded by per-slot lengths (empty pages and
      unoccupied slots are skipped), GROUP-indexed cross attention
      (``grouped_cross_attention`` gathers each slot's group row), and
      ``slot_decode_sample`` (greedy / temperature / top-k per
      ``sampler``; finished slots emit eos and freeze). Fetch
      ``token_name`` for the per-step ``[S, 1]`` sampled ids.
    * ``table_prog`` (feeds ``slot_idx``, ``page_row``): rewrite one
      slot's page-table row — mid-flight page extension before a
      dispatch, and the release/rollback paths' reset to the trash
      page.

    ``beam_width=K`` (K >= 2) builds the BEAM variant: the slots become
    ``S / K`` beam LANES of K aligned hypotheses, the step program runs
    ``slot_beam_search`` instead of the sampler — one ``lax.top_k``
    lattice per lane, the same ``beam_step`` the dense
    ``beam_search`` op uses — and the per-step hypothesis reorder is
    executed IN-GRAPH as a parent gather of the page-table rows (plus
    tok/pos/done/score), so the host's only reorder work is refcount
    rebinds: a pure parent permutation moves ZERO KV bytes. Beam adds
    the ``pgd_score [S, 1]`` accumulated-log-prob state (admit/join
    gain a ``start_score [1, 1]`` feed: 0 for the lane's hypothesis 0,
    -1e9 for the rest — the first-step duplicate suppression the dense
    lattice convention uses), done hypotheses' KV writes are routed to
    the trash page in-graph (a frozen hypothesis must never write a
    page a survivor may share), and the last return value is a dict of
    fetch names — ``{"token", "parent", "score", "logits"}`` — instead
    of the single token name (the session fetches the first three;
    ``logits`` is the offline-lattice test hook).

    ``speculative=K`` (K >= 1, sampler mode only) ALSO builds the
    speculative verify program — the tree-attention dispatch that
    scores the anchor plus K host-drafted tokens in one target forward
    and commits the longest accepted prefix in-graph:

    * ``spec_step_prog`` (feeds ``spec_draft [S, K]`` draft tokens,
      ``spec_parent [S, N]`` tree parents and ``spec_anc [S, N, N]``
      ancestor mask, N = K + 1 with node 0 the anchor): embeds all N
      tree nodes at their LOGICAL positions (``pos + depth``), writes
      every node's K/V into the slot's write pages at storage
      ``pos .. pos + N - 1`` (``paged_spec_kv_write``; done slots
      trash-route), runs ``paged_tree_attention`` (committed prefix +
      ancestor path per node), then ``slot_speculative_accept`` — the
      sequential sampler replayed down the tree, sharing
      ``sample_step_tokens`` + ``slot_lifecycle_advance`` so committed
      streams are bit-identical to the plain step program — and
      finally ``paged_spec_kv_compact`` per layer to gather the
      accepted path's K/V rows into canonical storage positions.
      The return value grows to ``(init, admit, join, prefill, table,
      step, spec_step, fetches)`` with ``fetches = {"token":
      <step tok>, "spec_token_seq": [S, N], "spec_accept_len":
      [S, 1]}`` — the plain ``step_prog`` stays available as the
      ``FLAGS_speculative=off`` oracle.

    Build under the training ``build()``'s fresh ``unique_name`` scope;
    parameters bind by name. All decode state is ``pgd_``-prefixed, so
    a paged and a dense session can coexist in one scope. Host-side
    page/group/cache allocation lives in
    ``serving.generation.SlotDecodeSession`` +
    ``serving.kv_pool``.
    """
    from paddle_tpu import unique_name

    from paddle_tpu.kernels.paged_attention import pages_for

    nn = fluid.layers
    S, T, D = int(num_slots), int(max_length), int(d_model)
    dh = D // n_head
    ps = int(page_size)
    npp = pages_for(T, ps)  # pages per slot at full length
    P = int(num_pages) if num_pages else 1 + S * npp
    pool_shape = [P, ps, n_head * dh]  # whole token rows a page
    G = int(num_groups) if num_groups else S
    K = int(beam_width)
    if K < 1:
        raise ValueError("beam_width must be >= 1, got %d" % K)
    beam = K > 1
    if beam and S % K:
        raise ValueError(
            "beam_width=%d does not tile num_slots=%d into aligned "
            "beam lanes" % (K, S))

    def heads(x):
        return nn.transpose(
            nn.reshape(x, shape=[0, 0, n_head, dh]), perm=[0, 2, 1, 3])

    samp = _sampler_attrs(sampler)
    if beam and samp["strategy"] != "greedy":
        raise ValueError(
            "beam_width > 1 replaces token sampling with the beam "
            "lattice — a stochastic sampler (%r) cannot compose with "
            "it" % (samp["strategy"],))
    n_spec = int(speculative)
    if n_spec < 0:
        raise ValueError("speculative must be >= 0, got %d" % n_spec)
    if n_spec and beam:
        raise ValueError(
            "speculative decode verifies the SAMPLER stream — it does "
            "not compose with beam_width > 1 (the lattice already "
            "scores full hypothesis sets per step)")

    with unique_name.guard({}):
        init = fluid.Program()
        init_startup = fluid.Program()
        with _setup_span("init", init), \
                fluid.program_guard(init, init_startup):
            blk = init.global_block()

            def persist(name, value, dtype="float32"):
                out = blk.create_var(name=name, shape=None, dtype=dtype,
                                     persistable=True)
                nn.assign(value, output=out)

            pe = nn.data("pe_table", shape=[T, D], dtype="float32",
                         append_batch_size=False)
            persist("pgd_pe_table", pe)
            mask0 = nn.fill_constant([G, T], "float32", 0.0)
            mask0 = nn.dynamic_update_slice(
                mask0, nn.fill_constant([G, 1], "float32", 1.0),
                nn.fill_constant([1], "int64", 0), axis=1)
            persist("pgd_src_mask", mask0)
            for i in range(n_layer):
                for kind in ("kcross", "vcross"):
                    persist("pgd_%s_%d" % (kind, i),
                            nn.fill_constant([G, n_head, T, dh],
                                             "float32", 0.0))
                for kind in ("kpool", "vpool"):
                    persist("pgd_%s_%d" % (kind, i),
                            nn.fill_constant(pool_shape, "float32", 0.0))
            persist("pgd_group_of",
                    nn.fill_constant([S, 1], "int64", 0), "int64")
            persist("pgd_table",
                    nn.fill_constant([S, npp], "int64", 0), "int64")
            persist("pgd_pos",
                    nn.fill_constant([S, 1], "int64", 0), "int64")
            persist("pgd_tok",
                    nn.fill_constant([S, 1], "int64", bos_id), "int64")
            persist("pgd_done",
                    nn.fill_constant([S, 1], "int64", 1), "int64")
            if beam:
                persist("pgd_score",
                        nn.fill_constant([S, 1], "float32", 0.0))

        with _setup_span("admit/1"):
            admit = _name_program(_build_admit_prog(
                1, S, T, D, G, npp, n_layer, n_head, d_inner,
                src_vocab_size, beam))

        join = fluid.Program()
        join_startup = fluid.Program()
        with _setup_span("join", join), \
                fluid.program_guard(join, join_startup):
            blk = join.global_block()
            _register_member(blk, S, npp, _slot_state_feeds(npp, beam))

        prefill = fluid.Program()
        prefill_startup = fluid.Program()
        # the prefill program re-creates the decoder's param-owning
        # layers (norms/fcs) exactly like the step program will; a
        # FRESH name scope gives both the training build's .w_0/.w_1
        # parameter suffixes instead of shifting each other's counters
        with _setup_span("prefill", prefill), unique_name.guard({}), \
                fluid.program_guard(prefill, prefill_startup):
            blk = prefill.global_block()
            pword = nn.data("prefix_word", shape=[T], dtype="int64")
            plen = nn.data("prefix_len", shape=[1], dtype="int64")
            wfrom = nn.data("write_from", shape=[1], dtype="int64")
            slot = nn.data("slot_idx", shape=[1], dtype="int64",
                           append_batch_size=False)
            gidx = nn.data("group_idx", shape=[1], dtype="int64",
                           append_batch_size=False)

            def pvar(name, shape, dtype="float32"):
                return blk.create_var(name=name, shape=shape, dtype=dtype,
                                      persistable=True)

            row = nn.gather(pvar("pgd_table", [S, npp], "int64"),
                            slot)  # [1, npp]
            mask_row = nn.gather(pvar("pgd_src_mask", [G, T]),
                                 gidx)  # [1, T]
            pe_all = nn.reshape(pvar("pgd_pe_table", [T, D]),
                                shape=[1, T, D])
            emb = nn.embedding(
                input=pword, size=[trg_vocab_size, D],
                param_attr=fluid.ParamAttr(name="trg_emb"))  # [1, T, D]
            h = nn.elementwise_add(nn.scale(emb, scale=D ** 0.5), pe_all)
            for i in range(n_layer):
                name = "dec_%d" % i
                kpool = pvar("pgd_kpool_%d" % i, pool_shape)
                vpool = pvar("pgd_vpool_%d" % i, pool_shape)
                nx = _prenorm(h, name + "_sattn")
                k1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_k"))
                v1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_v"))
                # every layer's K/V for the whole prefix lands in one op;
                # positions below write_from (prefix-cache hits) and the
                # pad tail route to the trash page
                fluid.layers.paged_kv_prefill(
                    kpool, vpool, k1, v1, row, wfrom, plen)
                if i == n_layer - 1:
                    break  # deeper layers don't exist: the rest of this
                    # block's compute feeds nothing
                q = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                bias_attr=False, name=name + "_smha_q"))
                att = fluid.layers.scaled_dot_product_attention(
                    q, k1, v1, causal=True, sm_scale=dh ** -0.5)
                att = nn.reshape(nn.transpose(att, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    att, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_smha_o"))
                nx2 = _prenorm(h, name + "_cattn")
                q2 = heads(nn.fc(nx2, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name=name + "_cmha_q"))
                kc = nn.gather(pvar("pgd_kcross_%d" % i,
                                    [G, n_head, T, dh]), gidx)
                vc = nn.gather(pvar("pgd_vcross_%d" % i,
                                    [G, n_head, T, dh]), gidx)
                ctx = fluid.layers.scaled_dot_product_attention(
                    q2, kc, vc, mask=mask_row, sm_scale=dh ** -0.5)
                ctx = nn.reshape(nn.transpose(ctx, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    ctx, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_cmha_o"))
                ff = _ffn(_prenorm(h, name + "_ffn"), D, d_inner,
                          name + "_ffn")
                h = nn.elementwise_add(h, ff)

        table = fluid.Program()
        table_startup = fluid.Program()
        with _setup_span("release/1", table), \
                fluid.program_guard(table, table_startup):
            blk = table.global_block()
            slot = nn.data("slot_idx", shape=[1], dtype="int64",
                           append_batch_size=False)
            page_row = nn.data("page_row", shape=[npp], dtype="int64")
            t = blk.create_var(name="pgd_table", shape=[S, npp],
                               dtype="int64", persistable=True)
            nn.dynamic_update_slice(t, page_row, slot, axis=0, out=t)

        step = fluid.Program()
        step_startup = fluid.Program()
        with _setup_span("step", step), \
                fluid.program_guard(step, step_startup):
            blk = step.global_block()

            def pvar(name, shape, dtype="float32"):
                return blk.create_var(name=name, shape=shape, dtype=dtype,
                                      persistable=True)

            tok = pvar("pgd_tok", [S, 1], "int64")
            pos = pvar("pgd_pos", [S, 1], "int64")
            done = pvar("pgd_done", [S, 1], "int64")
            ptable = pvar("pgd_table", [S, npp], "int64")
            group_of = pvar("pgd_group_of", [S, 1], "int64")
            pe_table = pvar("pgd_pe_table", [T, D])
            src_mask = pvar("pgd_src_mask", [G, T])
            # resident tokens per slot AFTER this step's write: pos + 1
            # for LIVE slots, 0 for dead ones — a zero length makes the
            # ragged kernel skip the slot outright (its logits are
            # garbage either way: nothing reads a dead slot's tokens),
            # so empty slots cost neither FLOPs nor page traffic and the
            # grid accounting models exactly what the step runs
            live_row = _live_row(done, ptable, S)
            lengths = nn.elementwise_mul(
                fluid.layers.increment(pos, value=1, in_place=False),
                live_row)
            if beam:
                score = pvar("pgd_score", [S, 1])
                # a DONE hypothesis's KV write routes to the trash
                # page: after a reorder it may share its write page
                # with a survivor (both adopted one parent's rows), and
                # frozen hypotheses are never attended past their last
                # live write — so the masked write is pure hygiene that
                # keeps shared page bits immutable without a COW
                write_table = nn.elementwise_mul(ptable, live_row)
            else:
                # sampler slots COW their write page while live and are
                # released before any sharing can alias a done slot's
                # frozen position — the dense write path is unchanged
                write_table = ptable
            emb = nn.embedding(
                input=tok, size=[trg_vocab_size, D],
                param_attr=fluid.ParamAttr(name="trg_emb"))
            emb = nn.reshape(emb, shape=[0, 1, D])  # [S, 1, D]
            pe_row = nn.reshape(
                nn.gather(pe_table, nn.reshape(pos, shape=[-1])),
                shape=[0, 1, D])
            h = nn.elementwise_add(nn.scale(emb, scale=D ** 0.5), pe_row)
            for i in range(n_layer):
                name = "dec_%d" % i
                kpool = pvar("pgd_kpool_%d" % i, pool_shape)
                vpool = pvar("pgd_vpool_%d" % i, pool_shape)
                nx = _prenorm(h, name + "_sattn")
                q = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                bias_attr=False, name=name + "_smha_q"))
                k1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_k"))
                v1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False, name=name + "_smha_v"))
                kpool, vpool = fluid.layers.paged_kv_write(
                    kpool, vpool, k1, v1, write_table, pos)
                att = fluid.layers.paged_attention(
                    q, kpool, vpool, ptable, lengths,
                    sm_scale=dh ** -0.5)
                att = nn.reshape(nn.transpose(att, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    att, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_smha_o"))
                nx2 = _prenorm(h, name + "_cattn")
                q2 = heads(nn.fc(nx2, dh * n_head, num_flatten_dims=2,
                                 bias_attr=False,
                                 name=name + "_cmha_q"))
                # group-indexed cross attention: each slot's row is its
                # GROUP's — N forked slots read one [H, T, dh] row
                ctx = fluid.layers.grouped_cross_attention(
                    q2, pvar("pgd_kcross_%d" % i, [G, n_head, T, dh]),
                    pvar("pgd_vcross_%d" % i, [G, n_head, T, dh]),
                    group_of, src_mask, sm_scale=dh ** -0.5,
                    live=live_row)
                ctx = nn.reshape(nn.transpose(ctx, perm=[0, 2, 1, 3]),
                                 shape=[0, 0, n_head * dh])
                h = nn.elementwise_add(h, nn.fc(
                    ctx, D, num_flatten_dims=2, bias_attr=False,
                    name=name + "_cmha_o"))
                ff = _ffn(_prenorm(h, name + "_ffn"), D, d_inner,
                          name + "_ffn")
                h = nn.elementwise_add(h, ff)
            h = _prenorm(h, "dec_final")
            logits = nn.fc(h, trg_vocab_size, num_flatten_dims=2,
                           name="proj_logits")
            if beam:
                (tok_new, pos_new, done_new, score_new,
                 parent) = fluid.layers.slot_beam_search(
                    logits, tok, pos, done, score, beam_width=K,
                    eos_id=eos_id, max_length=T)
                # THE zero-copy reorder: each surviving hypothesis
                # adopts its parent's page-table ROW in-graph (the op
                # already parent-gathered pos/done and selected the
                # survivor's token/score), so the device-side cost of a
                # hypothesis reshuffle is an [S, npp] int gather — the
                # host only rebinds refcounts, and COW fires later only
                # if a duplicated parent's WRITE page gets written
                nn.assign(nn.gather(ptable,
                                    nn.reshape(parent, shape=[-1])),
                          output=ptable)
                nn.assign(score_new, output=score)
            else:
                tok_new, pos_new, done_new = \
                    fluid.layers.slot_decode_sample(
                        logits, pos, done=done, eos_id=eos_id,
                        max_length=T, **samp)
            # thread the loop state: the NEXT scan iteration embeds the
            # token sampled here, no host in the loop
            nn.assign(tok_new, output=tok)
            nn.assign(pos_new, output=pos)
            nn.assign(done_new, output=done)

        if n_spec:
            Nn = n_spec + 1
            spec = fluid.Program()
            spec_startup = fluid.Program()
            # like prefill: the spec program re-creates the decoder's
            # param-owning layers, so a FRESH name scope keeps the
            # .w_0/.w_1 parameter suffixes aligned with the training
            # build instead of shifting the outer scope's counters
            with _setup_span("spec", spec), unique_name.guard({}), \
                    fluid.program_guard(spec, spec_startup):
                blk = spec.global_block()

                def pvar(name, shape, dtype="float32"):
                    return blk.create_var(name=name, shape=shape,
                                          dtype=dtype, persistable=True)

                # concrete shapes (no -1 batch dim): the slot axis is
                # fixed at S, and shape inference downstream (concat
                # with [S, 1] vars, broadcasts against [S, 1] pos)
                # needs it static
                draft = nn.data("spec_draft", shape=[S, n_spec],
                                dtype="int64",
                                append_batch_size=False)  # [S, K]
                par = nn.data("spec_parent", shape=[S, Nn],
                              dtype="int64",
                              append_batch_size=False)    # [S, N]
                anc = nn.data("spec_anc", shape=[S, Nn, Nn],
                              dtype="int64",
                              append_batch_size=False)    # [S, N, N]
                tok = pvar("pgd_tok", [S, 1], "int64")
                pos = pvar("pgd_pos", [S, 1], "int64")
                done = pvar("pgd_done", [S, 1], "int64")
                ptable = pvar("pgd_table", [S, npp], "int64")
                group_of = pvar("pgd_group_of", [S, 1], "int64")
                pe_table = pvar("pgd_pe_table", [T, D])
                src_mask = pvar("pgd_src_mask", [G, T])
                live_row = _live_row(done, ptable, S)
                # the tree kernel's ragged bound: committed storage for
                # a LIVE slot is [0, pos) and its tree occupies storage
                # pos .. pos + N - 1; -1 marks a dead slot (zero output
                # rows, no pages scanned)
                base = nn.elementwise_sub(
                    nn.elementwise_mul(
                        fluid.layers.increment(pos, value=1,
                                               in_place=False),
                        live_row),
                    nn.fill_constant([S, 1], "int64", 1))
                # a done slot's whole tree writes to the trash page
                write_table = nn.elementwise_mul(ptable, live_row)
                nodes_tok = nn.concat([tok, draft], axis=1)  # [S, N]
                # depth of node i = |ancestors| - 1 (anc carries the
                # diagonal and the anchor column), so its LOGICAL
                # sequence position is pos + depth — clamped into the
                # PE table exactly like the sequential position clamp
                depth = nn.elementwise_sub(
                    nn.reduce_sum(anc, dim=2),               # [S, N]
                    nn.fill_constant([1, 1], "int64", 1))
                logical = nn.elementwise_min(
                    nn.elementwise_add(pos, depth),
                    nn.fill_constant([1, 1], "int64", T - 1))
                pe_rows = nn.reshape(
                    nn.gather(pe_table,
                              nn.reshape(logical, shape=[-1])),
                    shape=[S, Nn, D])
                emb = nn.embedding(
                    input=nodes_tok, size=[trg_vocab_size, D],
                    param_attr=fluid.ParamAttr(name="trg_emb"))
                h = nn.elementwise_add(nn.scale(emb, scale=D ** 0.5),
                                       pe_rows)
                spec_pools = []
                for i in range(n_layer):
                    name = "dec_%d" % i
                    kpool = pvar("pgd_kpool_%d" % i, pool_shape)
                    vpool = pvar("pgd_vpool_%d" % i, pool_shape)
                    nx = _prenorm(h, name + "_sattn")
                    q = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                                    bias_attr=False,
                                    name=name + "_smha_q"))
                    k1 = heads(nn.fc(nx, dh * n_head,
                                     num_flatten_dims=2,
                                     bias_attr=False,
                                     name=name + "_smha_k"))
                    v1 = heads(nn.fc(nx, dh * n_head,
                                     num_flatten_dims=2,
                                     bias_attr=False,
                                     name=name + "_smha_v"))
                    kpool, vpool = fluid.layers.paged_spec_kv_write(
                        kpool, vpool, k1, v1, write_table, pos)
                    spec_pools.append((kpool, vpool))
                    att = fluid.layers.paged_tree_attention(
                        q, kpool, vpool, ptable, base, anc,
                        sm_scale=dh ** -0.5, max_length=T)
                    att = nn.reshape(
                        nn.transpose(att, perm=[0, 2, 1, 3]),
                        shape=[0, 0, n_head * dh])
                    h = nn.elementwise_add(h, nn.fc(
                        att, D, num_flatten_dims=2, bias_attr=False,
                        name=name + "_smha_o"))
                    nx2 = _prenorm(h, name + "_cattn")
                    q2 = heads(nn.fc(nx2, dh * n_head,
                                     num_flatten_dims=2,
                                     bias_attr=False,
                                     name=name + "_cmha_q"))
                    ctx = fluid.layers.grouped_cross_attention(
                        q2,
                        pvar("pgd_kcross_%d" % i, [G, n_head, T, dh]),
                        pvar("pgd_vcross_%d" % i, [G, n_head, T, dh]),
                        group_of, src_mask, sm_scale=dh ** -0.5,
                        live=live_row)
                    ctx = nn.reshape(
                        nn.transpose(ctx, perm=[0, 2, 1, 3]),
                        shape=[0, 0, n_head * dh])
                    h = nn.elementwise_add(h, nn.fc(
                        ctx, D, num_flatten_dims=2, bias_attr=False,
                        name=name + "_cmha_o"))
                    ff = _ffn(_prenorm(h, name + "_ffn"), D, d_inner,
                              name + "_ffn")
                    h = nn.elementwise_add(h, ff)
                h = _prenorm(h, "dec_final")
                spec_logits = nn.fc(h, trg_vocab_size,
                                    num_flatten_dims=2,
                                    name="proj_logits")  # [S, N, V]
                (spec_anchor, spec_seq, spec_acc, spec_path, spec_pos,
                 spec_done) = fluid.layers.slot_speculative_accept(
                    spec_logits, nodes_tok, par, pos, done,
                    eos_id=eos_id, max_length=T, **samp)
                # survivor commit AFTER the walk (attention read the
                # pre-commit tree layout) and BEFORE the state assigns
                for kpool, vpool in spec_pools:
                    fluid.layers.paged_spec_kv_compact(
                        kpool, vpool, write_table, pos, spec_path,
                        spec_acc)
                nn.assign(spec_anchor, output=tok)
                nn.assign(spec_pos, output=pos)
                nn.assign(spec_done, output=done)
    if beam:
        fetches = {"token": tok_new.name, "parent": parent.name,
                   "score": score_new.name, "logits": logits.name}
        return init, admit, join, prefill, table, step, fetches
    if n_spec:
        fetches = {"token": tok_new.name,
                   "spec_token_seq": spec_seq.name,
                   "spec_accept_len": spec_acc.name}
        return init, admit, join, prefill, table, step, spec, fetches
    return init, admit, join, prefill, table, step, tok_new.name


def build_draft_decoder(
    num_slots,
    trg_vocab_size=1000,
    max_length=64,
    n_head=4,
    d_model=128,
    d_inner=None,
    page_size=8,
    num_pages=None,
    eos_id=2,
):
    """The small DRAFT transformer for speculative decoding: a 1-layer
    decoder-only LM (no cross attention — cheapness is the point) that
    shares the target's token embedding (``trg_emb``) and position
    table (``pgd_pe_table``) and runs over the SAME paged geometry —
    its own K/V pools ``pgd_draft_{k,v}pool_0 [P, ps, H * dh]`` indexed
    through the target's ``pgd_table`` row per slot, so draft cache
    residency exactly tracks slot page residency with zero extra
    bookkeeping.

    Host-driven single-token steps: ``step_prog`` feeds
    ``draft_tok``/``draft_pos``/``draft_live`` ``[S, 1]`` and fetches
    the greedy next token ``[S, 1]`` (non-live rows write to the trash
    page, attend nothing and emit eos). The serving drafter replays
    each slot's committed tokens through this program to keep the
    draft cache current, then rolls K draft steps ahead of the anchor.

    Correctness is structurally independent of this model: the accept
    walk re-samples every committed token from TARGET logits, so a
    stale or even randomly-initialised draft (its ``draft_dec_*`` /
    ``draft_proj_logits`` params are NOT part of the target training
    build) only lowers the acceptance rate. For the same reason the
    draft pools deliberately sit OUTSIDE copy-on-write: after a fork
    repoints a page, the fork's draft rows for that page are garbage
    until rewritten — harmless, never target-visible.

    Returns ``(init_prog, step_prog, step_startup_prog, token_name)``;
    ``init_prog`` zero-allocates the draft pools and must run after the
    paged decoder's ``init_prog`` (it reuses the session scope).
    ``step_startup_prog`` carries the initializers for EVERY param the
    step program touches — including the shared ``trg_emb`` — so a
    session must run it selectively (only vars the scope is missing),
    the way ``serving.speculative.DraftModelDrafter`` does.
    """
    from paddle_tpu import unique_name

    from paddle_tpu.kernels.paged_attention import pages_for

    nn = fluid.layers
    S, T, D = int(num_slots), int(max_length), int(d_model)
    dh = D // int(n_head)
    ps = int(page_size)
    npp = pages_for(T, ps)
    P = int(num_pages) if num_pages else 1 + S * npp
    pool_shape = [P, ps, n_head * dh]
    di = int(d_inner) if d_inner else 2 * D

    def heads(x):
        return nn.transpose(
            nn.reshape(x, shape=[0, 0, n_head, dh]), perm=[0, 2, 1, 3])

    with unique_name.guard({}):
        init = fluid.Program()
        init_startup = fluid.Program()
        with fluid.program_guard(init, init_startup):
            blk = init.global_block()
            for kind in ("kpool", "vpool"):
                out = blk.create_var(name="pgd_draft_%s_0" % kind,
                                     shape=None, dtype="float32",
                                     persistable=True)
                nn.assign(nn.fill_constant(pool_shape, "float32", 0.0),
                          output=out)

        step = fluid.Program()
        step_startup = fluid.Program()
        with fluid.program_guard(step, step_startup):
            blk = step.global_block()

            def pvar(name, shape, dtype="float32"):
                return blk.create_var(name=name, shape=shape,
                                      dtype=dtype, persistable=True)

            dtok = nn.data("draft_tok", shape=[S, 1], dtype="int64",
                           append_batch_size=False)
            dpos = nn.data("draft_pos", shape=[S, 1], dtype="int64",
                           append_batch_size=False)
            dlive = nn.data("draft_live", shape=[S, 1], dtype="int64",
                            append_batch_size=False)
            ptable = pvar("pgd_table", [S, npp], "int64")
            pe_table = pvar("pgd_pe_table", [T, D])
            kpool = pvar("pgd_draft_kpool_0", pool_shape)
            vpool = pvar("pgd_draft_vpool_0", pool_shape)
            ddone = nn.elementwise_sub(
                nn.fill_constant([S, 1], "int64", 1), dlive)
            lengths = nn.elementwise_mul(
                fluid.layers.increment(dpos, value=1, in_place=False),
                dlive)
            write_table = nn.elementwise_mul(ptable, dlive)
            emb = nn.embedding(
                input=dtok, size=[trg_vocab_size, D],
                param_attr=fluid.ParamAttr(name="trg_emb"))
            emb = nn.reshape(emb, shape=[0, 1, D])
            pe_row = nn.reshape(
                nn.gather(pe_table, nn.reshape(dpos, shape=[-1])),
                shape=[0, 1, D])
            h = nn.elementwise_add(nn.scale(emb, scale=D ** 0.5),
                                   pe_row)
            nx = _prenorm(h, "draft_dec_sattn")
            q = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                            bias_attr=False, name="draft_dec_smha_q"))
            k1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                             bias_attr=False, name="draft_dec_smha_k"))
            v1 = heads(nn.fc(nx, dh * n_head, num_flatten_dims=2,
                             bias_attr=False, name="draft_dec_smha_v"))
            kpool, vpool = fluid.layers.paged_kv_write(
                kpool, vpool, k1, v1, write_table, dpos)
            att = fluid.layers.paged_attention(
                q, kpool, vpool, ptable, lengths, sm_scale=dh ** -0.5)
            att = nn.reshape(nn.transpose(att, perm=[0, 2, 1, 3]),
                             shape=[0, 0, n_head * dh])
            h = nn.elementwise_add(h, nn.fc(
                att, D, num_flatten_dims=2, bias_attr=False,
                name="draft_dec_smha_o"))
            ff = _ffn(_prenorm(h, "draft_dec_ffn"), D, di,
                      "draft_dec_ffn")
            h = nn.elementwise_add(h, ff)
            h = _prenorm(h, "draft_final")
            logits = nn.fc(h, trg_vocab_size, num_flatten_dims=2,
                           name="draft_proj_logits")
            dtok_new, _dpos_new, _ddone_new = \
                fluid.layers.slot_decode_sample(
                    logits, dpos, done=ddone, eos_id=eos_id,
                    max_length=T)
    return init, step, step_startup, dtok_new.name


def build_cow_batch_prog(num_slots, max_length, n_layer, n_head,
                         d_model, page_size, num_pages, pairs):
    """One COALESCED copy-on-write dispatch: copy ``pairs`` KV page
    pairs across every layer's pools and install the affected slots'
    repointed table rows — all in ONE executable, where the per-pair
    ``copy_prog`` would cost ``pairs`` dispatches (beam reorders
    multiply COW pairs per step, so the dispatch count is the hot-path
    number; tests pin it).

    The program takes its window WHOLE, it does not unroll it: one
    ``paged_copy_page`` a layer takes the ``src_pages`` / ``dst_pages``
    vectors (a gather of every source page, then a scatter onto the
    destinations) and one row scatter installs the rows, so a program
    holds ``n_layer + 1`` operators at every rung and building it costs
    the same at 512 pairs as at 1. Every source is read before any
    destination is written; ``serving.generation._check_cow_window``
    holds a window to what makes that equal to copying in order.

    Feeds: ``src_pages``/``dst_pages``/``slot_idxs`` ``[pairs]`` int64
    and ``page_rows [pairs, npp]`` — each pair's slot with that slot's
    FINAL row (a slot with several pairs in one window repeats its
    final row; the repeated scatter writes equal values). Pad short
    windows with ``(src=0, dst=0)`` trash-page self-copies bound to a
    live slot's unchanged row — bit-neutral by construction. Copies all
    run before any repoint (the copy-before-repoint COW discipline, batch
    edition). ``pairs`` is a bucket-ladder rung
    (``analysis.lint.suggest_buckets`` discipline): the session builds
    one program per rung and pads up, so the executable set stays
    finite and warm. Built under a fresh ``unique_name`` scope so the
    structural fingerprint is identical whenever the geometry is —
    rung programs are content-addressed across sessions."""
    from paddle_tpu import unique_name

    from paddle_tpu.kernels.paged_attention import pages_for

    nn = fluid.layers
    S, T = int(num_slots), int(max_length)
    dh = int(d_model) // int(n_head)
    ps = int(page_size)
    npp = pages_for(T, ps)
    P = int(num_pages)
    pool_shape = [P, ps, n_head * dh]
    n = int(pairs)
    if n < 1:
        raise ValueError("build_cow_batch_prog needs pairs >= 1")
    with unique_name.guard({}):
        prog = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(prog, startup):
            blk = prog.global_block()
            src_pages = nn.data("src_pages", shape=[n], dtype="int64",
                                append_batch_size=False)
            dst_pages = nn.data("dst_pages", shape=[n], dtype="int64",
                                append_batch_size=False)
            slot_idxs = nn.data("slot_idxs", shape=[n], dtype="int64",
                                append_batch_size=False)
            page_rows = nn.data("page_rows", shape=[n, npp],
                                dtype="int64", append_batch_size=False)
            for i in range(n_layer):
                kpool = blk.create_var(name="pgd_kpool_%d" % i,
                                       shape=pool_shape,
                                       dtype="float32", persistable=True)
                vpool = blk.create_var(name="pgd_vpool_%d" % i,
                                       shape=pool_shape,
                                       dtype="float32", persistable=True)
                fluid.layers.paged_copy_page(
                    kpool, vpool, src_pages, dst_pages)
            _write_rows(blk, "pgd_table", [S, npp], page_rows, slot_idxs,
                        n, "int64")
    return prog


def build_admit_batch_prog(rows, num_slots, src_vocab_size=1000,
                           max_length=64, n_layer=2, n_head=4, d_model=128,
                           d_inner=512, page_size=8, num_groups=None):
    """One admission dispatch for ``rows`` queued sources (the sibling of
    :func:`build_cow_batch_prog`; ``SlotDecodeSession.admit_pending``
    batches the head of its queue through it): the paged decoder's
    ``admit_prog`` with ``rows`` rows a feed — ``src_word [rows, T]``,
    ``src_len [rows, 1]``, ``slot_idx`` / ``group_idx [rows]``,
    ``page_row [rows, npp]``, ``start_tok`` / ``start_pos [rows, 1]`` —
    one encoder forward, each layer's cross K/V and the source mask
    scattered into the rows' groups, the rows' slots registered. A row of
    padding carries ``group_idx = num_groups`` and ``slot_idx =
    num_slots``: past the end, so its writes are dropped and an
    all-padding call leaves every ``pgd_`` array as it was. ``rows`` is a
    rung of the session's ladder (rung 1 is ``admit_prog`` itself). Built
    under a fresh ``unique_name`` scope, so parameters bind by the
    training build's names and equal geometry gives an equal
    fingerprint."""
    from paddle_tpu import unique_name

    from paddle_tpu.kernels.paged_attention import pages_for

    rows = int(rows)
    if rows < 2:
        raise ValueError(
            "build_admit_batch_prog needs rows >= 2 (one row is "
            "build_paged_slot_decoder's admit_prog)")
    S, T = int(num_slots), int(max_length)
    G = int(num_groups) if num_groups else S
    with unique_name.guard({}):
        return _build_admit_prog(
            rows, S, T, int(d_model), G, pages_for(T, int(page_size)),
            n_layer, n_head, d_inner, src_vocab_size)


def build_table_batch_prog(rows, num_slots, max_length=64, page_size=8):
    """One table dispatch for ``rows`` slots (the sibling of
    :func:`build_admit_batch_prog`; ``SlotDecodeSession`` repoints the
    slots one release hands it through it): ``table_prog`` with ``rows``
    rows a feed -- ``slot_idx [rows]``, ``page_row [rows, npp]`` -- one
    row scatter into ``pgd_table``. A row of padding carries ``slot_idx =
    num_slots``: past the end, so its write is dropped and an all-padding
    call leaves the table as it was. ``rows`` is a rung of the session's
    ladder (rung 1 is ``table_prog`` itself). Built under a fresh
    ``unique_name`` scope, so equal geometry gives an equal
    fingerprint."""
    from paddle_tpu import unique_name

    from paddle_tpu.kernels.paged_attention import pages_for

    rows = int(rows)
    if rows < 2:
        raise ValueError(
            "build_table_batch_prog needs rows >= 2 (one row is "
            "build_paged_slot_decoder's table_prog)")
    nn = fluid.layers
    npp = pages_for(int(max_length), int(page_size))
    with unique_name.guard({}):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            slot = nn.data("slot_idx", shape=[rows], dtype="int64",
                           append_batch_size=False)
            page_row = nn.data("page_row", shape=[npp], dtype="int64")
            _write_rows(prog.global_block(), "pgd_table",
                        [int(num_slots), npp], page_row, slot, rows,
                        "int64")
    return prog


def save_compiled_generator(dirname, batch_size, src_vocab_size,
                            trg_vocab_size, max_length, n_layer, n_head,
                            d_model, d_inner, scope=None, bos_id=1,
                            eos_id=2, platforms=None):
    """AOT artifact for GENERATION serving (the level users deploy):
    the entire KV-cached greedy decode — encoder prepare plus a
    lax.scan over the cached step, caches as loop carry — compiled into
    ONE XLA executable with the trained parameters baked in as
    constants. Written in io.save_compiled_inference_model's on-disk
    format, so io.load_compiled_inference_model (and the C++
    ptpu_aot_generator main) serve it with no program IR, no parameter
    files, no per-token host round trip and no tracing at serve time.

    Feeds: src_word int32 [B, max_length], src_len int32 [B, 1].
    Fetch: generated_tokens int32 [B, max_length] — the exact token
    stream cached_greedy_generate produces (pinned by
    tests/test_aot_generation.py against the committed generation
    golden). Reference anchor: inference/api/api_impl.cc serving +
    RecurrentGradientMachine's generation role (SURVEY §2.8), fused
    into one compiled program the TPU way.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.lowering import BlockLowerer, build_step_fn
    from paddle_tpu.executor import global_scope
    from paddle_tpu.io import _write_compiled_artifact

    scope = scope or global_scope()
    prepare, step, logits_name = build_cached_decoder(
        batch_size, src_vocab_size, trg_vocab_size, max_length,
        n_layer, n_head, d_model, d_inner)
    B, T, D = int(batch_size), int(max_length), int(d_model)
    # kernel lowering is platform-keyed (same invariant
    # save_compiled_inference_model enforces): one artifact per platform
    if platforms is not None and len(platforms) > 1:
        raise ValueError(
            "save_compiled_generator: kernel lowering is platform-keyed; "
            "export one artifact per platform instead of %r"
            % (platforms,))
    platform = (list(platforms)[0] if platforms
                else jax.default_backend())

    gen_names = {"gen_src_mask"}
    for i in range(n_layer):
        for kind in ("kcross", "vcross", "kcache", "vcache"):
            gen_names.add("gen_%s_%d" % (kind, i))
    cache_names = {n for n in gen_names if "cache" in n}

    scope_names = scope.visible_names()  # parent scopes too
    prep_lower = BlockLowerer(prepare, 0, is_test=True)
    p_in, p_out = prep_lower.analyze(scope_names,
                                     {"src_word", "src_len"})
    prep_fn = build_step_fn(prepare, ["src_word", "src_len"], [], p_in,
                            p_out, is_test=True, platform=platform)
    # the step program reads the gen_* vars prepare wrote: analyze with
    # them present, exactly as the scope looks after a prepare run
    step_lower = BlockLowerer(step, 0, is_test=True)
    s_in, s_out = step_lower.analyze(
        scope_names | gen_names, {"cur_tok", "pe_row", "gen_pos"})
    step_fn = build_step_fn(step, ["cur_tok", "pe_row", "gen_pos"],
                            [logits_name], s_in, s_out, is_test=True,
                            platform=platform)

    params = {}
    for n in sorted(set(p_in) | (set(s_in) - gen_names)):
        val = scope.get_value(n)
        if val is None:
            raise RuntimeError(
                "save_compiled_generator: parameter %r not in scope "
                "(train or load params first)" % n)
        params[n] = jnp.asarray(val)

    pe_table = jnp.asarray(position_encoding_table(T, D))

    def generate(src_word, src_len):
        key = jax.random.PRNGKey(0)
        prep_state, _ = prep_fn(
            dict(params), {"src_word": src_word, "src_len": src_len},
            key)
        frozen = dict(params)
        caches0 = {}
        for n in gen_names:
            (caches0 if n in cache_names else frozen)[n] = prep_state[n]
        trg0 = jnp.full((B, T), eos_id, jnp.int32).at[:, 0].set(bos_id)
        done0 = jnp.zeros((B,), jnp.bool_)

        def body(carry, t):
            caches, trg, done = carry
            state = dict(frozen)
            state.update(caches)
            cur = jax.lax.dynamic_slice(trg, (0, t), (B, 1))
            pe = jax.lax.dynamic_slice(pe_table, (t, 0), (1, D))
            pe = jnp.broadcast_to(pe[None], (B, 1, D))
            new_state, fetches = step_fn(
                state,
                {"cur_tok": cur, "pe_row": pe,
                 "gen_pos": jnp.reshape(t, (1,))},
                key)
            nxt = jnp.argmax(fetches[0][:, 0, :], axis=-1)
            nxt = nxt.astype(jnp.int32)
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            trg = jax.lax.dynamic_update_slice(trg, nxt[:, None],
                                               (0, t + 1))
            done = done | (nxt == eos_id)
            caches = {n: new_state[n] for n in caches}
            return (caches, trg, done), None

        (_, trg, _), _ = jax.lax.scan(
            body, (caches0, trg0, done0),
            jnp.arange(T - 1, dtype=jnp.int32))
        # tuple, not bare array: CompiledInferenceModel.run iterates
        # the call result as the fetch list
        return (trg,)

    specs = (jax.ShapeDtypeStruct((B, T), jnp.int32),
             jax.ShapeDtypeStruct((B, 1), jnp.int32))
    kwargs = {"platforms": list(platforms)} if platforms else {}
    exported = jax.export.export(jax.jit(generate), **kwargs)(*specs)
    _write_compiled_artifact(
        dirname, exported, ["src_word", "src_len"],
        {"src_word": ((B, T), "int32"), "src_len": ((B, 1), "int32")},
        ["generated_tokens"])
    return logits_name
