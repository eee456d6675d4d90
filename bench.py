"""Benchmark harness: ResNet-50 + Transformer training throughput and MFU,
plus the serving legs.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"models": {...each requested model...}}. It measures the CHIP: every
worker resolves the accelerator first (``fluid.require_accelerator``) and
a host without one is an error that names the missing device — there is
no CPU retry and no splice of older records. The exit code is non-zero
when any requested model produced no result.

``BENCH_PLATFORM=cpu`` is the explicit CPU proxy the CI stages use
(tools/run_ci.sh ``bench``/``perfgate``, tools/decode_smoke.py): tiny
shapes, metric names suffixed ``_cpu_proxy``, ``mfu`` null. A proxy
checks the bench's own control flow and counters; it is never a device
number.

Structure: the parent process never imports jax (a chip belongs to one
process at a time, and a parent that has touched JAX holds it). It runs
each model's bench in its own worker subprocess (``bench.py --worker``,
model via env) under a timeout and merges the workers' JSON into the
single output line.

Baseline: the reference's best committed ResNet-50 train throughput —
84.08 img/s (MKL-DNN BS256 on 2x Xeon 6148, benchmark/IntelOptimizedPaddle.md:40-46;
no GPU/Fluid ResNet numbers are committed in-tree, see BASELINE.md).

Measurement design:
- Input comes from the in-graph ``random_data_generator`` reader op
  (reference capability: operators/reader/create_random_data_generator_op.cc)
  so the bench measures the framework's training step, not the host link.
- Mixed precision: the bf16 AMP rewrite (transpiler/amp_transpiler.py) is
  on by default on the chip; master weights stay f32 (BENCH_AMP=0 disables).
- The timed loop fetches nothing per step (steps chain on device through
  donated state); one loss fetch at the end syncs the pipeline and is
  included in the timing. Finiteness of that loss is asserted.
- JAX's persistent compile cache is on (core/exec_cache.enable_xla_cache:
  ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``);
  ``compile_seconds_cold``/``_warm`` say what this run paid.
"""

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 84.08
# ResNet-50 @224 forward: 7.76 GFLOP per image at the HARDWARE convention
# (2 FLOPs per multiply-accumulate — the same convention the chips' peak
# FLOP/s are quoted in). The widely cited "4.1 GFLOPs" counts
# multiply-adds as one op (GMACs). Audit trail: the per-conv signature
# table from tools/hlo_cost_model.py sums to 7.71 GF conv + 0.05 GF fc
# fwd on this exact model; fwd+bwd ~= 3x forward (dx+dw each ~= fwd).
# The transformer's 6N accounting below is in the same convention.
# Peaks come from the one table, observability/telemetry.CHIP_PEAKS.
TRAIN_GFLOP_PER_IMG = 3 * 7.76


def _timed_steps(exe, main_prog, loss, steps, warmup, feed=None):
    """Warmup + timed run. Prefers the compiled multi-step path (one
    lax.scan executable per K steps, no per-step host dispatch); falls
    back to the per-step loop if the program can't scan. Returns
    (seconds, last_loss, mode) — mode records which path actually ran so
    a silent fallback can't masquerade as a multi-step measurement."""
    feed = feed or {}
    # default per-step: measured equal on TPU (async dispatch already hides
    # per-step host cost: 2517 vs 2530 img/s) and 4x slower on XLA:CPU
    # (scan bodies lose intra-op parallelism); the capability itself is
    # tested in tests/test_multi_step.py and pays off when dispatch is
    # synchronous (multi-host barriers, very small step times)
    use_multi = os.environ.get("BENCH_MULTISTEP", "0") == "1"
    if use_multi:
        try:
            # warmup at the SAME step count: the scan executable is keyed
            # on K, so a different K would recompile inside the timing
            exe.run_multi_step(main_prog, steps, feed=feed,
                               fetch_list=[loss])
            t0 = time.perf_counter()
            out = exe.run_multi_step(main_prog, steps, feed=feed,
                                     fetch_list=[loss])
            dt = time.perf_counter() - t0
            return dt, float(np.ravel(np.asarray(out[0]))[0]), "multi-step"
        except (RuntimeError, TypeError) as e:
            # not scannable: state_out ⊄ state_in, a scan carry type
            # mismatch, or an XLA compile failure — fall back LOUDLY
            print("multi-step path failed (%s: %s); falling back to "
                  "per-step" % (type(e).__name__, e), file=sys.stderr)
    for _ in range(warmup):
        exe.run(main_prog, feed=feed, fetch_list=[])
    exe.run(main_prog, feed=feed, fetch_list=[loss])
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        exe.run(main_prog, feed=feed, fetch_list=[])
    out = exe.run(main_prog, feed=feed, fetch_list=[loss])
    dt = time.perf_counter() - t0
    return dt, float(np.ravel(np.asarray(out[0]))[0]), "per-step"


def _bench_resnet(fluid, on_tpu, use_amp):
    from paddle_tpu.models import resnet
    from paddle_tpu.transpiler import rewrite_program_amp

    # Full ImageNet shapes on TPU; scaled-down proxy on CPU (CI smoke).
    if on_tpu:
        img, bs, steps, warmup = 224, 128, 50, 10
    else:
        img, bs, steps, warmup = 64, 16, 5, 2
    bs = int(os.environ.get("BENCH_BS", bs))  # batch-sweep override
    # BENCH_DATA=host feeds real numpy batches through the PyReader path
    # (h2d transfer on the timed path; BENCH_DOUBLE_BUFFER=0 disables the
    # device prefetch so the overlap win is measurable). Default "graph"
    # keeps the in-graph generator: the framework step, not the host link.
    # BENCH_UINT8=1 ships the pixels as uint8 and normalizes ON DEVICE —
    # a 4x smaller h2d transfer, the input-pipeline recipe for real TPU
    # hosts (and the fix VERDICT r2 named for the host-link-bound mode).
    host_data = os.environ.get("BENCH_DATA", "graph") == "host"
    double_buffer = os.environ.get("BENCH_DOUBLE_BUFFER", "1") == "1"
    uint8_input = os.environ.get("BENCH_UINT8", "0") == "1"

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 5
    startup.random_seed = 5
    with fluid.program_guard(main_prog, startup):
        if host_data:
            pixel = fluid.layers.data(
                name="bench_pixel", shape=[3, img, img],
                dtype="uint8" if uint8_input else "float32")
            label = fluid.layers.data(
                name="bench_label", shape=[1], dtype="int64")
            if uint8_input:
                # cast + scale to [0,1) on device; XLA fuses this into the
                # first conv's input so it costs one pass over the batch
                pixel = fluid.layers.scale(
                    fluid.layers.cast(pixel, "float32"), scale=1.0 / 255.0)
        else:
            pixel, label = fluid.layers.random_data_generator(
                shapes=[[bs, 3, img, img], [bs, 1]],
                dtypes=["float32", "int64"],
                int_high=999,
            )
        predict = resnet.resnet_imagenet(pixel, 1000, depth=50)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        loss = fluid.layers.mean(cost)
        fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    if use_amp:
        rewrite_program_amp(main_prog, "bfloat16")

    place = fluid.TPUPlace() if on_tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)
    if host_data:
        dt, lv = _host_data_steps(
            fluid, exe, main_prog, loss, steps, warmup, bs, img, place,
            double_buffer, uint8_input)
        mode = ("host-data"
                + ("+double-buffer" if double_buffer else "")
                + ("+uint8" if uint8_input else ""))
    else:
        dt, lv, mode = _timed_steps(exe, main_prog, loss, steps, warmup)
    assert np.isfinite(lv), "non-finite loss %r" % lv
    img_per_sec = steps * bs / dt
    return {
        "metric": "resnet50_train_throughput" + ("" if on_tpu else "_cpu_proxy"),
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_S, 3),
        "gflop_per_unit": TRAIN_GFLOP_PER_IMG,
        "rate": img_per_sec,
        "mode": mode,
    }


def _host_data_steps(fluid, exe, main_prog, loss, steps, warmup, bs, img,
                     place, double_buffer, uint8_input=False):
    """Timed loop fed per-step from a PyReader over pre-generated numpy
    batches: the h2d transfer is ON the timed path, so the double-buffer
    prefetch delta (and the uint8 4x-smaller-transfer delta) is what
    this mode exists to measure."""
    rng = np.random.RandomState(13)
    n_distinct = 8  # enough to defeat any transfer caching, bounded RAM

    def make_pixels():
        if uint8_input:
            return rng.randint(0, 256, (bs, 3, img, img), dtype="uint8")
        return rng.rand(bs, 3, img, img).astype("float32")

    batches = [
        {"bench_pixel": make_pixels(),
         "bench_label": rng.randint(0, 999, (bs, 1)).astype("int64")}
        for _ in range(n_distinct)
    ]

    def make_reader(n):
        def reader():
            for i in range(n):
                yield batches[i % n_distinct]
        return reader

    # dict batches bypass feed slots, so the PyReader is constructed bare
    # (py_reader() would append unused slot vars to the default program)
    from paddle_tpu.layers.io import PyReader

    pyreader = PyReader([], capacity=4, use_double_buffer=double_buffer)

    pyreader.decorate_paddle_reader(make_reader(warmup))
    pyreader.start(place=place if double_buffer else None)
    for _ in range(warmup):
        exe.run(main_prog, feed=pyreader.next_feed(), fetch_list=[])
    pyreader.reset()

    pyreader.decorate_paddle_reader(make_reader(steps))
    # clock starts BEFORE reader start in both modes: the double buffer's
    # head-start transfers are part of what the comparison measures
    t0 = time.perf_counter()
    pyreader.start(place=place if double_buffer else None)
    for _ in range(steps - 1):
        exe.run(main_prog, feed=pyreader.next_feed(), fetch_list=[])
    out = exe.run(main_prog, feed=pyreader.next_feed(), fetch_list=[loss])
    dt = time.perf_counter() - t0
    pyreader.reset()
    return dt, float(np.ravel(np.asarray(out[0]))[0])


def _bench_transformer(fluid, on_tpu, use_amp):
    """Transformer-base-ish NMT train throughput in tokens/sec (the
    BASELINE.md 'Transformer base NMT train MFU' config, single chip).
    No reference throughput number is committed in-tree (BASELINE.md),
    so vs_baseline is null; MFU is the comparable figure."""
    from paddle_tpu.models import transformer
    from paddle_tpu.transpiler import rewrite_program_amp

    if on_tpu:
        bs, seq, steps, warmup = 64, 256, 30, 5
        n_layer, n_head, d_model, d_inner = 6, 8, 512, 2048
    else:
        bs, seq, steps, warmup = 4, 32, 4, 2
        n_layer, n_head, d_model, d_inner = 2, 4, 64, 128
    vocab = 32000 if on_tpu else 500
    bs = int(os.environ.get("BENCH_BS", bs))  # batch-sweep override
    seq = int(os.environ.get("BENCH_SEQ", seq))
    # vocab override: lets the CPU proxy run the real 32k vocab head at
    # small bs/seq — the default 500-vocab proxy is insensitive to what
    # the loss head costs
    vocab = int(os.environ.get("BENCH_VOCAB", vocab))
    # depth override: MFU stays a valid per-model measurement since the
    # FLOP accounting below scales with n_layer
    n_layer = int(os.environ.get("BENCH_LAYERS", n_layer))

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main_prog, startup):
        loss, feeds, _ = transformer.build(
            src_vocab_size=vocab, trg_vocab_size=vocab, max_length=seq,
            n_layer=n_layer, n_head=n_head, d_model=d_model,
            d_inner=d_inner, dropout=0.1,
        )
        fluid.optimizer.Adam(learning_rate=2e-4).minimize(loss)
    if use_amp:
        rewrite_program_amp(main_prog, "bfloat16")

    rng = np.random.RandomState(11)
    feed = {
        "src_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "src_len": np.full((bs, 1), seq, "int64"),
        "trg_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "trg_len": np.full((bs, 1), seq, "int64"),
        "label": rng.randint(1, vocab, (bs, seq)).astype("int64"),
    }
    feed = {k: v for k, v in feed.items()
            if any(f.name == k for f in feeds)}

    place = fluid.TPUPlace() if on_tpu else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)
    dt, lv, mode = _timed_steps(exe, main_prog, loss, steps, warmup,
                                feed=feed)
    assert np.isfinite(lv), "non-finite loss %r" % lv
    # decoder tokens/sec (standard NMT accounting); with src_len == trg_len
    # each decoder token corresponds to one src token of encoder work, so
    # charging enc+dec params per decoder token is exact, not double-counted
    tok_per_sec = steps * bs * seq / dt
    # 6N rule (2N fwd + 4N bwd) on non-embedding params; attention
    # score/context FLOPs are excluded, so MFU is slightly conservative
    n_params = (
        n_layer * (4 * d_model * d_model + 2 * d_model * d_inner)  # enc
        + n_layer * (8 * d_model * d_model + 2 * d_model * d_inner)  # dec
    )
    gflop_per_tok = 3 * 2 * n_params / 1e9
    return {
        "metric": "transformer_train_throughput" + ("" if on_tpu else "_cpu_proxy"),
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "gflop_per_unit": gflop_per_tok,
        "rate": tok_per_sec,
        "mode": mode,
    }


def _bench_serving(fluid, on_tpu):
    """Serving-throughput leg: the deterministic mixed-batch-size load
    from serving/loadgen.py (the SAME code path tools/serve_smoke.py
    smoke-tests) replayed through a warm BatchingServer — so the bench
    trajectory tracks requests/sec, batch occupancy and latency p50/p99
    alongside training MFU, and benchmark/budgets.json gates all three.
    """
    import shutil
    import tempfile

    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    from paddle_tpu.serving import BatchingServer, loadgen

    model_dir = tempfile.mkdtemp(prefix="bench_serving_")
    try:
        loadgen.build_demo_model(model_dir)
        predictor = create_paddle_predictor(
            NativeConfig(model_dir=model_dir, use_tpu=on_tpu))
        server = BatchingServer(predictor, max_batch=8, workers=2,
                                batch_linger_s=0.002)
        try:
            server.warmup()
            wall, ok, errors = loadgen.replay(
                server, loadgen.demo_requests(48), concurrency=4)
            assert ok == 48 and not errors, \
                "replay errors: %r" % errors[:3]
            rec = loadgen.serving_capture(server, ok, wall)
        finally:
            server.close()
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    rec["metric"] = "serving_throughput" + ("" if on_tpu else "_cpu_proxy")
    # requests aren't FLOP-accounted: rate feeds throughput, mfu stays None
    rec["rate"] = rec["value"]
    rec["gflop_per_unit"] = 0.0
    return rec


def _bench_frontend(fluid, on_tpu):
    """Network front-end leg (serving/frontend.py): the SAME mixed
    unary load as the serving leg, but replayed over a REAL loopback
    socket through ``ServingClient``s — so the bench trajectory tracks
    wire-level requests/sec and CLIENT-side latency p50/p99 (socket,
    framing and base64 codec included), plus the stream
    time-to-first-token of the decode endpoint. ``tools/run_ci.sh net``
    smoke-tests the same path cross-process with a warm cache;
    benchmark/budgets.json gates ttft_ms / latency_ms_p99 / throughput.
    """
    import shutil
    import tempfile

    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import (
        BatchingServer,
        ServingClient,
        ServingFrontend,
        loadgen,
    )
    from paddle_tpu.serving.generation import Sampler, SlotDecodeSession

    fcfg = dict(src_vocab_size=40, trg_vocab_size=40, n_layer=1,
                n_head=2, d_inner=64)
    seq, dmodel = 16, 32
    model_dir = tempfile.mkdtemp(prefix="bench_frontend_")
    try:
        loadgen.build_demo_model(model_dir)
        predictor = create_paddle_predictor(
            NativeConfig(model_dir=model_dir, use_tpu=on_tpu))
        server = BatchingServer(predictor, max_batch=8, workers=2,
                                batch_linger_s=0.002)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 13
        startup.random_seed = 13
        with fluid.program_guard(main, startup):
            transformer.build(dropout=0.0, label_smooth_eps=0.0,
                              max_length=seq, d_model=dmodel, **fcfg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        sess = SlotDecodeSession(
            exe, num_slots=4, max_length=seq, d_model=dmodel,
            paged=True, page_size=4, steps=2, sampler=Sampler(seed=3),
            **fcfg)
        fe = ServingFrontend(server=server, session=sess)
        try:
            server.warmup()
            rng = np.random.RandomState(17)
            src = rng.randint(3, 40, (4, seq)).astype("int64")
            warm_cl = ServingClient(fe.address)
            warm_cl.generate_full(src[0], src_len=seq)  # decode warmup
            # wire unary replay: one connection per synchronous caller
            latencies = []
            wall, ok, errors = loadgen.replay(
                lambda: ServingClient(fe.address),
                loadgen.demo_requests(48), concurrency=4,
                latencies=latencies)
            assert ok == 48 and not errors, \
                "wire replay errors: %r" % errors[:3]
            # stream ttft: request sent -> first token chunk received.
            # Request tracing rides the stream portion: each request's
            # completed trace (fetched back over the wire) feeds the
            # ttft_breakdown split — queue wait vs prefill vs first
            # decode dispatch — beside the raw client-side ttft_ms
            from paddle_tpu.observability import tracing

            ttfts, traces = [], []
            tracing.enable(True)
            try:
                for i in range(4):
                    t0 = time.perf_counter()
                    first = []

                    def see(ev, t0=t0, first=first):
                        if ev.get("event") == "tokens" and not first:
                            first.append(time.perf_counter() - t0)

                    warm_cl.generate_full(src[i], src_len=seq,
                                          on_event=see)
                    ttfts.extend(first)
                    traces.append(warm_cl.trace())
            finally:
                tracing.enable(False)
            warm_cl.close()
            rec = loadgen.wire_capture(ok, wall, latencies, ttfts,
                                       traces=traces)
        finally:
            fe.close()
            server.close()
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    rec["metric"] = ("frontend_throughput"
                     + ("" if on_tpu else "_cpu_proxy"))
    # wire requests aren't FLOP-accounted: rate feeds throughput only
    rec["rate"] = rec["value"]
    rec["gflop_per_unit"] = 0.0
    return rec


def _bench_decode(fluid, on_tpu):
    """Paged-decode A/B leg (ROADMAP item 3 / ragged paged attention):
    steady-state decode tokens/sec and per-token latency at MIXED slot
    lengths and LOW pool occupancy (4 requests in an 8-slot pool), the
    PR 8 dense slot decoder vs the block-paged session (page-table KV
    pool, ragged attention, steps=8 on-device token loop). The paged
    session's tokens are asserted equal to the dense oracle's inside
    the leg, so the gated speedup can never come from decoding less.
    ``predicted_hbm_bytes`` is the paged kernel's grid accounting at
    the leg's canonical mixed-length state — deterministic, gated hard:
    decode traffic must stay proportional to RESIDENT pages.

    PR 12 adds the cross-request-reuse legs: (a) a prefix-cache
    exercise (cold forced-prefix prefill, then a hit that must decode
    bit-identical — ``prefix_hit_rate``/``prefill_tokens_saved``), and
    (b) the best-of-N A/B — two sources x best-of-4 through
    ``admit_group`` (ONE encoder forward + one chunked prefill + joins
    per source, group-pooled cross K/V at ``num_groups=2``) vs eight
    UNSHARED solo admissions of the same members; both decode
    bit-identical token matrices (asserted, so ``bestofn_speedup``
    can never come from decoding less), and ``cross_kv_bytes`` is the
    grouped cross-pool footprint gated deterministically against the
    per-slot dense layout.

    PR 15 adds the BEAM A/B: ``beam_width=4`` decode with the
    zero-copy reorder (per-step parent permutation = in-graph
    page-table row gather + host refcount rebinds) vs the SAME session
    geometry under ``FLAGS_beam_reorder=reference`` (every survivor
    physically copies its parent's resident pages — the
    pre-paged-attention baseline). Both sessions share one program set
    (identical geometry, content-addressed executables) and decode
    bit-identical n-best matrices + scores (asserted), so
    ``beam_speedup`` is pure reorder mechanics. ``beam_reorder_bytes``
    is the rebind session's physically-moved reorder bytes, page-
    geometry-accounted (reorder copies — zero for pure permutations —
    plus write-page COW splits x page bytes); deterministic under
    greedy decode, gated hard: growth means reorders started copying
    or COW stopped being write-page-only.

    PR 16 adds the SPECULATIVE A/B: ``speculative={"k": 3}`` decode
    (ngram drafter, tree-attention verify — k + 1 tree nodes scored in
    ONE target dispatch) vs the SAME session under
    ``FLAGS_speculative=off`` (sequential ``steps=1`` decode, the
    bit-exactness oracle). The arm runs the prompt-lookup regime the
    drafter exists for: a briefly copy-trained model over periodic
    sources behind a forced prefix that seeds the suffix lookup (the
    drafter matches over emitted tokens + forced prefix). One session,
    one program set, a flag flip between waves; both arms decode
    bit-identical tokens (asserted), so ``speculative_speedup`` is
    pure dispatch amortization — tokens committed per target
    dispatch — and ``acceptance_rate`` is the drafter's measured
    accepted/proposed ratio over the timed wave.
    """
    from paddle_tpu.kernels import paged_attention as pk
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.generation import Sampler, SlotDecodeSession

    vocab, seq, dm, n_head, S, K, ps = 50, 32, 32, 2, 8, 8, 8
    cfg = dict(src_vocab_size=vocab, trg_vocab_size=vocab, n_layer=1,
               n_head=n_head, d_inner=64)
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main_prog, startup):
        transformer.build(dropout=0.0, label_smooth_eps=0.0,
                          max_length=seq, d_model=dm, **cfg)
    exe = fluid.Executor(fluid.TPUPlace() if on_tpu else fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(11)
    B = 4  # half the pool stays empty: the raggedness regime
    src = rng.randint(3, vocab, (B, seq)).astype("int64")
    mixed = [seq, seq // 2, seq // 4, 3]
    src_len = np.asarray(mixed, "int64")[:, None]

    def tokens_of(out):
        # decoded tokens per row: through the first eos, else the full
        # T-1 budget (deterministic — seeded weights, greedy decode)
        total = 0
        for row in out:
            hits = np.where(row[1:] == 2)[0]
            total += (int(hits[0]) + 1) if hits.size else (seq - 1)
        return total

    def timed(sess):
        sess.generate(src, src_len)  # warm every executable
        t0 = time.perf_counter()
        out = sess.generate(src, src_len)
        return tokens_of(out), time.perf_counter() - t0, out

    dense = SlotDecodeSession(exe, num_slots=S, max_length=seq,
                              d_model=dm, **cfg)
    d_tok, d_dt, d_out = timed(dense)
    paged = SlotDecodeSession(exe, num_slots=S, max_length=seq,
                              d_model=dm, paged=True, page_size=ps,
                              steps=K, prefix_cache_pages=16, **cfg)
    p_tok, p_dt, p_out = timed(paged)
    assert np.array_equal(d_out, p_out), \
        "paged decode diverged from the dense oracle"
    d_tps = d_tok / d_dt
    p_tps = p_tok / p_dt

    # --- prefix-cache exercise (greedy => slot-independent tokens):
    # a repeated forced prefix provisions by reference; the hit MUST
    # decode bit-identical to the cold prefill that cached the pages
    pfx = [int(t) for t in src[0][: 3 * seq // 4]]
    cold = paged.generate_best_of(src[0], 1, src_len=seq,
                                  prefix_tokens=pfx)
    hit = paged.generate_best_of(src[0], 1, src_len=seq,
                                 prefix_tokens=pfx)
    assert np.array_equal(cold, hit), \
        "prefix-cache hit diverged from the cold prefill"
    pstats = paged.prefix_cache_stats()

    # --- best-of-N shared vs unshared A/B: same members, same slots,
    # same (seed, slot, position) PRNG streams — bit-identical tokens,
    # so the ratio is pure admission/prefill amortization + group-
    # pooled cross K/V
    smp = Sampler(strategy="top_k", top_k=4, temperature=0.9, seed=13)
    N = 8  # best-of-N members, filling the pool from ONE source
    src_bo = rng.randint(3, vocab, (seq,)).astype("int64")
    pfx_bo = [int(t) for t in src_bo[: 3 * seq // 4]]

    def drain(sess, slots):
        outs = {}
        while len(outs) < len(slots):
            outs.update(sess.step())
        return np.stack([outs[s] for s in slots])

    def shared_wave(sess):
        return drain(sess, sess.admit_group(
            src_bo, N, src_len=seq, prefix_tokens=pfx_bo))

    def unshared_wave(sess):
        slots = [sess.admit(src_bo, seq, prefix_tokens=pfx_bo)
                 for _ in range(N)]
        return drain(sess, slots)

    mk = lambda groups: SlotDecodeSession(  # noqa: E731
        exe, num_slots=S, max_length=seq, d_model=dm, paged=True,
        page_size=ps, steps=K, num_groups=groups, sampler=smp, **cfg)
    sh, un = mk(2), mk(S)
    shared_wave(sh)  # warm every executable (admit/join/prefill/copy)
    unshared_wave(un)
    t0 = time.perf_counter()
    sh_out = shared_wave(sh)
    sh_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    un_out = unshared_wave(un)
    un_dt = time.perf_counter() - t0
    assert np.array_equal(sh_out, un_out), \
        "shared-KV best-of-N diverged from the unshared replay"
    bo_tok = tokens_of(sh_out)
    sh_tps = bo_tok / sh_dt
    un_tps = bo_tok / un_dt

    # --- beam A/B: zero-copy rebind reorder vs the copy-reorder
    # oracle. One geometry (the oracle's transient copies need page
    # headroom, so BOTH sessions get it — identical programs, shared
    # content-addressed executables), bit-identical n-bests asserted.
    from paddle_tpu import flags as _flags

    bw = 4
    beam_pages = 1 + 2 * S * (seq // ps)  # oracle copy headroom
    src_beam = rng.randint(3, vocab, (2, seq)).astype("int64")

    def mk_beam():
        return SlotDecodeSession(
            exe, num_slots=S, max_length=seq, d_model=dm, paged=True,
            page_size=ps, beam_width=bw, num_pages=beam_pages, **cfg)

    def beam_wave(sess):
        outs = [sess.generate_beam(r, seq) for r in src_beam]
        return outs

    rb = mk_beam()
    beam_wave(rb)  # warm (admit/join/beam-step/cow-batch executables)
    rb.beam_reorder_pages = 0
    rb.cow_pairs = 0
    t0 = time.perf_counter()
    rb_out = beam_wave(rb)
    rb_dt = time.perf_counter() - t0
    rb_moved = rb.beam_reorder_pages  # MUST stay 0: pure rebinds
    rb_cow = rb.cow_pairs
    assert rb_moved == 0, (
        "rebind beam reorder physically copied %d pages" % rb_moved)
    _flags.set_flag("beam_reorder", "reference")
    try:
        ref = mk_beam()
        beam_wave(ref)  # warm (same content-addressed programs)
        ref.beam_reorder_pages = 0
        t0 = time.perf_counter()
        ref_out = beam_wave(ref)
        ref_dt = time.perf_counter() - t0
        ref_moved = ref.beam_reorder_pages
    finally:
        _flags.set_flag("beam_reorder", "rebind")
    assert ref_moved > 0, "the copy oracle never copied a page"
    for (rt, rs), (ct, cs) in zip(rb_out, ref_out):
        assert np.array_equal(rt, ct) and np.array_equal(rs, cs), \
            "rebind beam diverged from the copy-reorder oracle"
    beam_tok = sum(tokens_of(rt) for rt, _ in rb_out)
    page_bytes = 2 * cfg["n_layer"] * n_head * ps * (dm // n_head) * 4
    beam_speedup = (beam_tok / rb_dt) / (beam_tok / ref_dt)

    # --- speculative A/B (PR 16): draft-then-verify vs the sequential
    # off-oracle on the SAME session — a flag flip between waves, so
    # the ratio is pure dispatch amortization over identical tokens.
    # The n-gram drafter only pays off when the decode stream actually
    # repeats, so this arm runs the prompt-lookup regime speculative
    # decoding exists for: a briefly copy-trained model over periodic
    # sources. Training runs LAST, in its own programs — every
    # deterministic budget above was captured before a weight moved.
    tr_main, tr_startup = fluid.Program(), fluid.Program()
    tr_main.random_seed = 21
    tr_startup.random_seed = 21
    # fresh unique_name scope: the training build must mint the SAME
    # param names as the leg's first build (the names every decode
    # session binds), or Adam would train a disconnected copy
    with fluid.program_guard(tr_main, tr_startup), \
            fluid.unique_name.guard({}):
        loss, _feeds, _extras = transformer.build(
            dropout=0.0, label_smooth_eps=0.0, max_length=seq,
            d_model=dm, **cfg)
        fluid.optimizer.Adam(learning_rate=0.003).minimize(loss)
    exe.run(tr_startup)
    trng = np.random.RandomState(22)
    for _ in range(300):
        ts = trng.randint(3, vocab, (16, seq)).astype("int64")
        ttrg = np.full_like(ts, 1)
        ttrg[:, 1:] = ts[:, :-1]
        full = np.full((16, 1), seq, "int64")
        exe.run(tr_main, feed={"src_word": ts, "src_len": full,
                               "trg_word": ttrg, "trg_len": full,
                               "label": ts}, fetch_list=[loss])
    motif = trng.randint(3, vocab, (B, 4)).astype("int64")
    src_sp = np.tile(motif, (1, seq // 4))
    # two periods of forced prefix: the drafter suffix-matches over
    # emitted tokens + forced prefix, so admission seeds the lookup
    # and the first verify already speculates at full acceptance
    pfx_sp = [[int(t) for t in row[:8]] for row in src_sp]

    spec = SlotDecodeSession(
        exe, num_slots=S, max_length=seq, d_model=dm, paged=True,
        page_size=ps, steps=1,
        speculative={"k": 3, "drafter": "ngram"}, **cfg)

    def spec_wave(sess):
        return drain(sess, [sess.admit(src_sp[i], seq,
                                       prefix_tokens=pfx_sp[i])
                            for i in range(B)])

    spec_wave(spec)  # warm the draft/tree-verify set
    _flags.set_flag("speculative", "off")
    try:
        spec_wave(spec)  # warm the sequential step too
        t0 = time.perf_counter()
        off_out = spec_wave(spec)
        off_dt = time.perf_counter() - t0
    finally:
        _flags.set_flag("speculative", "on")
    p0, a0 = spec.spec_proposed, spec.spec_accepted
    t0 = time.perf_counter()
    sp_out = spec_wave(spec)
    sp_dt = time.perf_counter() - t0
    assert np.array_equal(sp_out, off_out), \
        "speculative decode diverged from the sequential off-oracle"
    sp_tok = tokens_of(sp_out)
    accept_rate = ((spec.spec_accepted - a0) / (spec.spec_proposed - p0)
                   if spec.spec_proposed > p0 else 0.0)

    acc = pk.grid_accounting(mixed + [0] * (S - B), ps, n_head,
                             dm // n_head, seq, num_groups=2,
                             n_layer=cfg["n_layer"])
    return {
        "metric": "decode_tokens_per_sec" + ("" if on_tpu
                                             else "_cpu_proxy"),
        "value": round(p_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "dense_tokens_per_sec": round(d_tps, 1),
        "paged_speedup": round(p_tps / d_tps, 3),
        "token_latency_ms": round(1000.0 * p_dt / p_tok, 3),
        "predicted_hbm_bytes": acc["hbm_bytes"],
        "hbm_vs_dense_ratio": round(
            acc["hbm_bytes"] / acc["dense_hbm_bytes"], 4),
        "decode_steps_per_dispatch": K,
        "pool_occupancy": B / S,
        # cross-request reuse (PR 12): best-of-4 x 2 sources, shared
        # (admit_group: 1 encoder + 1 prefill + joins per source) vs
        # unshared (8 solo admissions), bit-identical token matrices
        "bestofn_speedup": round(sh_tps / un_tps, 3),
        "bestofn_tokens_per_sec": round(sh_tps, 1),
        "prefix_hit_rate": round(pstats["hit_rate"], 3),
        "prefill_tokens_saved": pstats["tokens_saved"],
        # grouped cross-pool footprint: [G=2, H, T, dh] per layer vs
        # the per-slot dense layout — deterministic, gated
        "cross_kv_bytes": acc["cross_hbm_bytes"],
        "cross_kv_dense_bytes": acc["cross_dense_hbm_bytes"],
        # beam A/B (PR 15): rebind-vs-copy tokens/sec ratio over
        # bit-identical n-bests, and the rebind wave's physically-moved
        # bytes (reorder copies — zero — plus write-page COW splits,
        # page-geometry-accounted). ref_reorder_bytes is the oracle's
        # O(resident) traffic for scale.
        "beam_speedup": round(beam_speedup, 3),
        "beam_tokens_per_sec": round(beam_tok / rb_dt, 1),
        "beam_reorder_bytes": (rb_moved + rb_cow) * page_bytes,
        "beam_ref_reorder_bytes": ref_moved * page_bytes,
        # speculative A/B (PR 16): draft-then-verify tokens/sec over
        # the sequential steps=1 off-oracle on the SAME session
        # (bit-identical tokens asserted), plus the drafter's measured
        # acceptance over the timed wave
        "speculative_speedup": round(
            (sp_tok / sp_dt) / (sp_tok / off_dt), 3),
        "speculative_tokens_per_sec": round(sp_tok / sp_dt, 1),
        "acceptance_rate": round(accept_rate, 3),
        "rate": p_tps,
        "gflop_per_unit": 0.0,
    }


def _worker_main():
    """One model bench in this process. Prints one JSON line.

    Resolves the device FIRST: the accelerator or fail, unless
    ``BENCH_PLATFORM=cpu`` asked for the explicit CPU proxy. Any failure
    — no accelerator included — propagates: the traceback goes to
    stderr, the exit code is non-zero and no result is printed. The
    orchestrator runs this under a timeout, so a hang is recoverable
    there.
    """
    model = os.environ.get("BENCH_MODEL", "resnet50")
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core import exec_cache
    from paddle_tpu.observability import telemetry

    on_tpu = os.environ.get("BENCH_PLATFORM") != "cpu"
    if on_tpu:
        device = fluid.require_accelerator()[0]
    else:
        jax.config.update("jax_platforms", "cpu")
        device = jax.devices()[0]
    exec_cache.enable_xla_cache()
    use_amp = os.environ.get("BENCH_AMP", "1" if on_tpu else "0") == "1"
    if model == "transformer":
        result = _bench_transformer(fluid, on_tpu, use_amp)
    elif model == "serving":
        result = _bench_serving(fluid, on_tpu)
    elif model == "frontend":
        result = _bench_frontend(fluid, on_tpu)
    elif model == "decode":
        result = _bench_decode(fluid, on_tpu)
    else:
        result = _bench_resnet(fluid, on_tpu, use_amp)
    # the one peak table (or an explicit FLAGS_peak_tflops); a CPU
    # proxy and a chip the table does not know get NO mfu
    peak = telemetry.peak_flops(device) if on_tpu else None
    rate = result.pop("rate")
    gflop = result.pop("gflop_per_unit")
    result["mfu"] = (round(rate * gflop * 1e9 / peak, 4)
                     if peak and gflop else None)
    # compile-tax telemetry (core/exec_cache.py): cold = seconds in
    # fresh XLA compiles, warm = seconds loading cached executables
    cache = exec_cache.stats()
    result["compile_seconds_cold"] = round(cache["compile_seconds_cold"], 3)
    result["compile_seconds_warm"] = round(cache["compile_seconds_warm"], 3)
    result["exec_cache"] = {
        "enabled": cache["enabled"],
        "xla_cache_dir": cache["xla_cache_dir"],
        "fresh_compiles": cache["fresh_compiles"],
        "persistent_hits": cache["persistent_hits"],
        "aot_hits": cache["aot_hits"],
    }
    # both models' gflop_per_unit count 2 FLOPs per MAC, matching the
    # peak's convention (see TRAIN_GFLOP_PER_IMG note)
    result["flop_convention"] = "2-per-mac"
    # flight-recorder view (observability/telemetry.py): step-time
    # percentiles + the telemetry-side MFU estimate, present only when
    # FLAGS_telemetry=1 (default off keeps the timed loop untouched —
    # the <2% overhead acceptance gate). Best-effort in its own try: an
    # observability failure (bad FLAGS_metrics_path etc.) must never
    # discard a fully measured bench result.
    try:
        if telemetry.ENABLED:
            st = telemetry.step_stats(peak=peak)
            result["step_ms"] = {
                "p50": round(st["p50_ms"], 3) if st["p50_ms"] else None,
                "p95": round(st["p95_ms"], 3) if st["p95_ms"] else None,
                "p99": round(st["p99_ms"], 3) if st["p99_ms"] else None,
            }
            result["mfu_telemetry"] = (
                round(st["mfu"], 4) if st["mfu"] else None)
            # HBM trajectory (observability/memory.py): measured ledger
            # watermark + the planner's prediction, so a capture tracks
            # footprint alongside MFU and tools/perf_diff.py can gate
            # regressions on it
            from paddle_tpu import profiler as _profiler

            ms = _profiler.memory_stats()
            result["peak_hbm_bytes"] = ms["measured_peak_bytes"]
            result["predicted_peak_bytes"] = ms["predicted_peak_bytes"]
            telemetry.flush()  # FLAGS_metrics_path scrape, if set
    except Exception as e:  # noqa: BLE001
        result["telemetry_error"] = "%s: %s" % (type(e).__name__, e)
    result["platform"] = device.platform
    result["device_kind"] = device.device_kind
    print(json.dumps(result))
    sys.stdout.flush()


def _run_isolated(argv, timeout_s, env=None):
    """Run argv in its own process GROUP with stdout/stderr captured to
    temp files; on timeout SIGKILL the whole group. Returns (rc, stdout,
    stderr) with rc=None on timeout.

    subprocess.run(capture_output=True, timeout=...) is NOT enough here:
    on timeout it kills only the direct child and then blocks in
    communicate() until pipe EOF — a grandchild that inherited the pipe
    (the serving legs spawn some) would hang the orchestrator. Files
    have EOF regardless, and the group kill leaves nothing running.
    """
    import signal
    import subprocess
    import tempfile

    with tempfile.TemporaryFile("w+", errors="replace") as fout, \
            tempfile.TemporaryFile("w+", errors="replace") as ferr:
        proc = subprocess.Popen(
            argv, stdout=fout, stderr=ferr, env=env, start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()
            rc = None
        fout.seek(0)
        ferr.seek(0)
        return rc, fout.read(), ferr.read()


def _run_worker(model, timeout_s):
    """Run one model bench in a subprocess (it inherits BENCH_PLATFORM);
    return (dict-or-None, err)."""
    env = dict(os.environ, BENCH_MODEL=model)
    rc, stdout, stderr = _run_isolated(
        [sys.executable, os.path.abspath(__file__), "--worker"],
        timeout_s, env=env,
    )
    sys.stderr.write(stderr[-8000:])
    if rc is None:
        return None, "timeout after %ds" % timeout_s
    if rc != 0:
        # the worker's own failure says what went wrong (a missing
        # accelerator names itself): its last stderr line
        last = [ln for ln in stderr.splitlines() if ln.strip()]
        return None, "worker rc=%d: %s" % (rc, last[-1] if last else "")
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line), None
    return None, "worker rc=0, no JSON on stdout"


MODELS = ("resnet50", "transformer", "serving", "frontend", "decode")


def main():
    """Orchestrate the model benches; print ONE JSON line. Returns the
    exit code: non-zero when a requested model produced no result."""
    worker_timeout = int(os.environ.get("BENCH_WORKER_TIMEOUT", 2700))
    # single-model BENCH_MODEL (the documented knob) still works;
    # BENCH_MODELS overrides with an explicit list
    models_env = os.environ.get(
        "BENCH_MODELS", os.environ.get("BENCH_MODEL", ",".join(MODELS)))
    models, errors = {}, {}
    for model in [m.strip() for m in models_env.split(",") if m.strip()]:
        if model not in MODELS:
            errors[model] = "unknown model (valid: %s)" % ", ".join(MODELS)
            continue
        result, err = _run_worker(model, worker_timeout)
        if result is not None:
            models[model] = result
        else:
            errors[model] = err

    primary = models.get("resnet50") or next(iter(models.values()), None)
    out = dict(primary or {})  # no result: no metric, no value
    out["models"] = models
    if os.environ.get("BENCH_PLATFORM") == "cpu":
        out["note"] = "cpu forced via BENCH_PLATFORM; values are cpu proxies"
    if errors:
        out["error"] = "; ".join("%s: %s" % kv for kv in sorted(errors.items()))
    print(json.dumps(out))
    sys.stdout.flush()

    # BENCH_LEDGER=<path> (or =1 for benchmark/perf_ledger.jsonl) appends
    # this capture as one trajectory point — every measured run lands in
    # the same append-only file tools/perf_ledger.py diff gates. Strictly
    # best-effort AFTER the JSON line is out: the capture must survive a
    # read-only checkout or a half-broken tools/ import.
    ledger_env = os.environ.get("BENCH_LEDGER")
    if ledger_env and models:
        try:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            import perf_ledger
            ledger = (perf_ledger.DEFAULT_LEDGER if ledger_env == "1"
                      else ledger_env)
            good = {name: m for name, m in models.items()
                    if isinstance(m, dict) and "error" not in m}
            if good:
                perf_ledger.append_entry(
                    ledger, good,
                    label=os.environ.get("BENCH_LEDGER_LABEL"),
                    source="bench.py")
                sys.stderr.write("bench: appended %d model(s) to %s\n"
                                 % (len(good), ledger))
        except Exception as e:
            sys.stderr.write("bench: ledger append failed (%s)\n" % e)
    return 1 if errors else 0


if __name__ == "__main__":
    if "--worker" in sys.argv[1:]:
        _worker_main()
    else:
        sys.exit(main())
