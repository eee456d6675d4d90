"""Continuous-batching serving engine tests: bit-exact batched parity
across mixed bucket shapes, admission control + deadline paths,
multi-tenant clones sharing one executable under concurrent load, the
slot-paged generation session's staggered-admission parity, the
FetchHandle deadline primitive, and the L001 bucket-ladder helper."""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis.lint import suggest_buckets
from paddle_tpu.core import exec_cache
from paddle_tpu.executor import FetchHandle, FetchTimeoutError
from paddle_tpu.inference import NativeConfig, create_paddle_predictor
from paddle_tpu.serving import (
    BatchingServer,
    DeadlineExceededError,
    QueueFullError,
    ServerClosedError,
    ServingError,
    loadgen,
)


@pytest.fixture(scope="module")
def demo_predictor(tmp_path_factory):
    # module-scoped: one train+save serves every server test (servers
    # clone it; weights are never written after load)
    path = str(tmp_path_factory.mktemp("serving") / "model")
    loadgen.build_demo_model(path)
    return create_paddle_predictor(
        NativeConfig(model_dir=path, use_tpu=False))


# -- bucketed batching: parity ----------------------------------------------

class _RecordingPredictor(object):
    """A predictor that keeps the feeds of every batch the server
    dispatched through it (or a clone of it), so a test can run the same
    batch again: the server does not say which bucket a request ran in."""

    def __init__(self, real, batches=None):
        self._real = real
        self.feed_names = real.feed_names
        self.feed_shapes = real.feed_shapes
        self.batches = [] if batches is None else batches

    def clone(self):
        return _RecordingPredictor(self._real.clone(), self.batches)

    def run(self, inputs):
        return self._real.run(inputs)

    def run_async(self, inputs):
        self.batches.append(inputs["x"])
        return self._real.run_async(inputs)

    def batch_of(self, x):
        """(batch, offset): where the rows ``x`` ran."""
        for batch in self.batches:
            for off in range(batch.shape[0] - x.shape[0] + 1):
                if np.array_equal(batch[off:off + x.shape[0]], x):
                    return batch, off
        raise AssertionError("no dispatched batch holds these rows")


def _assert_served_as_its_batch_computes(recorder, predictor, server, req,
                                         outs):
    """``outs`` is to the BIT what ``Predictor.run`` gives for the same
    rows in the padded bucket the server dispatched them in, and within
    2 ulp of float32 of the request alone at its own rung
    (``run_reference``): XLA's CPU dot rounds differently from one batch
    shape to the next, so across buckets coalescing is invisible to the
    ulp, not to the bit. The ulp is that of the row's largest value (the
    demo model ends in a softmax: a rounding of the logits moves every
    probability of the row by that much)."""
    rows = req["x"].shape[0]
    batch, off = recorder.batch_of(req["x"])
    same_batch = predictor.run({"x": batch})
    alone = server.run_reference(req)
    assert len(outs) == len(same_batch) == len(alone)
    for g, w, a in zip(outs, same_batch, alone):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w)[off:off + rows])
        a = np.asarray(a)
        ulp = np.spacing(np.abs(a).max(axis=-1, keepdims=True))
        assert (np.abs(np.asarray(g) - a) <= 2 * ulp).all()
    assert outs[0].shape[0] == rows  # pad sliced off
    return batch


def test_batched_results_bit_identical_across_mixed_buckets(
        demo_predictor):
    """Requests of every odd batch size, submitted concurrently so they
    coalesce into padded bucket batches, come back BIT-identical to the
    same rows run through ``Predictor.run`` at the bucket they ran in,
    and to 2 ulp what the request gives alone at its own rung."""
    recorder = _RecordingPredictor(demo_predictor)
    server = BatchingServer(recorder, max_batch=8, workers=2,
                            batch_linger_s=0.01)
    try:
        requests = loadgen.demo_requests(24)
        futures = [server.submit(r) for r in requests]
        got = [f.result(timeout=30) for f in futures]
        rungs = set(server.stats()["batch_buckets"])
        for req, outs in zip(requests, got):
            batch = _assert_served_as_its_batch_computes(
                recorder, demo_predictor, server, req, outs)
            assert batch.shape[0] in rungs
            if batch.shape[0] == req["x"].shape[0]:
                # alone and on a rung: the raw per-request run, to the bit
                for g, w in zip(outs, demo_predictor.run(req)):
                    np.testing.assert_array_equal(np.asarray(g),
                                                  np.asarray(w))
    finally:
        server.close()


def test_mixed_sizes_resolve_to_ladder_and_stop_compiling(
        demo_predictor):
    """After warmup over the bucket ladder, a mixed-batch-size load adds
    ZERO fresh compiles — the L001 mitigation, measured at the
    exec-cache counters."""
    server = BatchingServer(demo_predictor, max_batch=8, workers=1)
    try:
        assert server.warmup() == [2, 4, 8]
        before = exec_cache.stats()["fresh_compiles"]
        wall, ok, errors = loadgen.replay(
            server, loadgen.demo_requests(32), concurrency=4)
        assert ok == 32 and not errors
        assert exec_cache.stats()["fresh_compiles"] == before, (
            "steady-state mixed load paid fresh compiles")
        st = server.stats()
        assert st["batches"] >= 1
        assert st["latency_ms"]["p99_ms"] is not None
    finally:
        server.close()


def test_clone_multitenant_share_one_executable_under_load(
        demo_predictor):
    """4 worker threads = 4 Predictor clones; the content-addressed
    registry means the whole fleet compiles each bucket shape once."""
    server = BatchingServer(demo_predictor, max_batch=8, workers=4,
                            batch_linger_s=0.001)
    try:
        server.warmup()
        before = exec_cache.stats()["fresh_compiles"]
        wall, ok, errors = loadgen.replay(
            server, loadgen.demo_requests(48), concurrency=8)
        assert ok == 48 and not errors
        assert exec_cache.stats()["fresh_compiles"] == before
    finally:
        server.close()


# -- admission control -------------------------------------------------------

def test_queue_full_rejects_with_typed_error(demo_predictor):
    # a long linger below max_batch rows keeps the dispatcher holding
    # the batch open, so the queue observably fills
    server = BatchingServer(demo_predictor, max_batch=8,
                            max_queue_depth=2, batch_linger_s=5.0)
    try:
        f1 = server.submit({"x": np.zeros((1, 12), "float32")})
        f2 = server.submit({"x": np.zeros((1, 12), "float32")})
        with pytest.raises(QueueFullError):
            server.submit({"x": np.zeros((1, 12), "float32")})
        server.close(drain=True)  # drain skips the linger
        assert len(f1.result(timeout=30)[0]) == 1
        assert f2.done()
    finally:
        server.close()


def test_deadline_lapses_in_queue(demo_predictor):
    """A deadlined request stuck BEHIND a slow batch (the single worker
    is busy) is expired from the queue, never dispatched."""

    class SlowRun(object):
        def __init__(self, real):
            self._real = real
            self.feed_names = real.feed_names
            self.feed_shapes = real.feed_shapes

        def clone(self):
            return self

        def run(self, inputs):
            return self._real.run(inputs)

        def run_async(self, inputs):
            time.sleep(0.3)  # the worker is wedged on this batch
            return self._real.run_async(inputs)

    server = BatchingServer(SlowRun(demo_predictor), max_batch=8,
                            batch_linger_s=0.0, workers=1)
    try:
        first = server.submit({"x": np.zeros((1, 12), "float32")})
        fut = server.submit({"x": np.zeros((1, 12), "float32")},
                            deadline_s=0.05)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)
        assert len(first.result(timeout=30)[0]) == 1
    finally:
        server.close(drain=False)


def test_deadline_lapses_in_flight(demo_predictor):
    """A dispatched batch that outlives its latest deadline is rejected
    through FetchHandle.result(timeout=...) — the server stays live."""

    class SlowHandle(object):
        def __init__(self, inner):
            self._inner = inner

        def result(self, timeout=None):
            if timeout is not None:
                # device never 'ready' inside the deadline
                time.sleep(timeout)
                raise FetchTimeoutError(timeout, ["out"])
            return self._inner.result()

    class SlowPredictor(_RecordingPredictor):
        def clone(self):
            return self

        def run_async(self, inputs):
            return SlowHandle(
                _RecordingPredictor.run_async(self, inputs))

    slow = SlowPredictor(demo_predictor)
    server = BatchingServer(slow, max_batch=8, batch_linger_s=0.2)
    try:
        # both requests coalesce into ONE batch (the linger holds it):
        # the deadlined one must be rejected, the patient one must NOT
        # be collateral damage — the reusable handle serves it late
        patient = np.ones((1, 12), "float32")
        fut_patient = server.submit({"x": patient})
        fut_deadline = server.submit({"x": np.zeros((2, 12), "float32")},
                                     deadline_s=0.05)
        with pytest.raises(DeadlineExceededError):
            fut_deadline.result(timeout=30)
        out = fut_patient.result(timeout=30)
        batch = _assert_served_as_its_batch_computes(
            slow, demo_predictor, server, {"x": patient}, out)
        assert batch.shape[0] == 4   # 1 + 2 rows, padded up the ladder
        # the server survived: a fresh request still serves
        out = server.run({"x": np.ones((1, 12), "float32")})
        assert out[0].shape == (1, 3)
    finally:
        server.close()


def test_submit_validation_and_close_semantics(demo_predictor):
    server = BatchingServer(demo_predictor, max_batch=4)
    with pytest.raises(ServingError):
        server.submit({"x": np.zeros((5, 12), "float32")})  # > max_batch
    with pytest.raises(ServingError):
        server.submit({"wrong": np.zeros((1, 12), "float32")})
    with pytest.raises(ServingError):
        server.submit({"x": np.zeros((1, 7), "float32")})  # bad dim
    # positional (list) form works
    out = server.run([np.zeros((2, 12), "float32")])
    assert out[0].shape == (2, 3)
    server.close()
    with pytest.raises(ServerClosedError):
        server.submit({"x": np.zeros((1, 12), "float32")})


def test_pad_buckets_group_dynamic_lengths():
    """pad_buckets pads non-batch DYNAMIC dims up their ladder BEFORE
    grouping, so two different user lengths share one bucket signature
    (one executable). Mechanical check through the admission path, over
    a stub predictor declaring a variable-length feed."""

    class StubPredictor(object):
        feed_names = ["x"]
        feed_shapes = {"x": (-1, -1)}  # L001's classic dynamic dim

        def clone(self):
            return self

    ladders = ((1,), (8, 16))  # dim 1 buckets at 8 then 16
    server = BatchingServer(StubPredictor(), max_batch=4,
                            pad_buckets={"x": ladders})
    try:
        a, _ = server._normalize({"x": np.ones((1, 5), "float32")})
        b, _ = server._normalize({"x": np.ones((1, 8), "float32")})
        a = server._pad_request(a)
        b = server._pad_request(b)
        assert a["x"].shape == b["x"].shape == (1, 8)
        assert a["x"][0, 5:].sum() == 0  # padded with pad_value
        with pytest.raises(ServingError):
            server._pad_request(
                {"x": np.ones((1, 17), "float32")})  # above ladder top
    finally:
        server.close()


def test_deadline_inside_linger_dispatches_early(demo_predictor):
    """A request whose deadline lands inside the linger window must be
    DISPATCHED at once, not held open until it can only be rejected."""
    server = BatchingServer(demo_predictor, max_batch=8,
                            batch_linger_s=2.0)
    try:
        out = server.submit({"x": np.zeros((1, 12), "float32")},
                            deadline_s=0.5).result(timeout=30)
        assert out[0].shape == (1, 3)  # served, not deadline-rejected
    finally:
        server.close()


def test_warmup_covers_every_pad_rung(demo_predictor):
    """warmup compiles each pad-ladder rung (cartesian with the batch
    ladder), so lower rungs aren't left cold."""

    class ShapeRecorder(object):
        feed_names = ["x"]
        feed_shapes = {"x": (-1, -1)}
        feed_dtypes = {"x": "float32"}

        def __init__(self):
            self.shapes = []

        def clone(self):
            return self

        def run(self, inputs):
            self.shapes.append(inputs["x"].shape)
            return [np.zeros((inputs["x"].shape[0], 2), "float32")]

    rec = ShapeRecorder()
    server = BatchingServer(rec, max_batch=4,
                            pad_buckets={"x": ((1,), (4, 8))})
    try:
        server.warmup()
        assert set(rec.shapes) == {
            (b, d) for b in (2, 4) for d in (4, 8)}
    finally:
        server.close()


def test_batch_reduced_fetch_is_a_typed_error(demo_predictor):
    """A fetch whose leading dim isn't the batch rows cannot be sliced
    per request — the server must say so, not return garbage."""

    class PooledPredictor(object):
        feed_names = ["x"]
        feed_shapes = {"x": (-1, 12)}

        def clone(self):
            return self

        def run(self, inputs):
            return [inputs["x"].sum(axis=0, keepdims=True)]  # [1, 12]

        def run_async(self, inputs):
            outs = self.run(inputs)

            class H(object):
                def result(self, timeout=None):
                    return outs

            return H()

    server = BatchingServer(PooledPredictor(), max_batch=4)
    try:
        with pytest.raises(ServingError, match="leading dim"):
            server.run({"x": np.ones((2, 12), "float32")})
        with pytest.raises(ServingError, match="leading dim"):
            server.run_reference({"x": np.ones((2, 12), "float32")})
    finally:
        server.close()


# -- FetchHandle deadline primitive -----------------------------------------

class _LazyArray(object):
    """Array-like whose readiness the test controls."""

    def __init__(self, value):
        self._value = value
        self.ready = False

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None):
        arr = np.asarray(self._value)
        return arr.astype(dtype) if dtype else arr


def test_fetch_handle_timeout_is_typed_and_reusable():
    arr = _LazyArray([1.0, 2.0])
    handle = FetchHandle([arr], ["out"])
    t0 = time.perf_counter()
    with pytest.raises(FetchTimeoutError) as exc:
        handle.result(timeout=0.05)
    assert time.perf_counter() - t0 < 5.0
    assert exc.value.fetch_names == ["out"]
    # nothing was consumed: once the device work lands, the SAME handle
    # still materializes
    arr.ready = True
    (out,) = handle.result(timeout=1.0)
    np.testing.assert_array_equal(out, [1.0, 2.0])
    (again,) = handle.result()  # memoized
    np.testing.assert_array_equal(again, out)


def test_fetch_handle_timeout_on_real_dispatch():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.reduce_sum(fluid.layers.scale(x, 2.0), dim=[1])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.arange(8, dtype="float32").reshape(2, 4)}
    handle = exe.run_async(main, feed=feed, fetch_list=[y])
    (got,) = handle.result(timeout=30.0)
    (want,) = exe.run(main, feed=feed, fetch_list=[y])
    np.testing.assert_array_equal(got, np.asarray(want))


# -- suggest_buckets (the L001 mitigation) ----------------------------------

def test_suggest_buckets_sizes():
    assert suggest_buckets([3, 5, 9, 17]) == (4, 8, 16, 32)
    assert suggest_buckets(range(1, 9)) == (1, 2, 4, 8)
    assert suggest_buckets([7]) == (8,)
    # thinning drops the SMALL rungs, keeps the top
    assert suggest_buckets([1, 300], max_buckets=3) == (128, 256, 512)


def test_suggest_buckets_shapes_and_dict():
    ladders = suggest_buckets([(4, 32), (2, 48), (8, 32)])
    assert ladders == ((2, 4, 8), (32, 64))
    by_feed = suggest_buckets({"src": [3, 70], "bs": [1, 4]})
    assert by_feed == {"src": (16, 32, 64, 128), "bs": (1, 2, 4)}
    with pytest.raises(ValueError):
        suggest_buckets([])
    with pytest.raises(ValueError):
        suggest_buckets([(1, 2), (1, 2, 3)])  # mixed ranks
    with pytest.raises(ValueError):
        suggest_buckets([0, 4])


def test_l001_hint_names_the_mitigation():
    from paddle_tpu.analysis.lint import lint

    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        x = fluid.layers.data("txt", shape=[-1, 16], dtype="float32")
        fluid.layers.reduce_sum(x)
    diags = [d for d in lint(prog) if d.rule == "L001"]
    assert diags and any("suggest_buckets" in (d.hint or "")
                         for d in diags)


# -- slot-paged generation ---------------------------------------------------

def _copy_task_batch(rng, bs, seq, vocab):
    src = rng.randint(3, vocab, (bs, seq)).astype("int64")
    trg = np.full_like(src, 1)
    trg[:, 1:] = src[:, :-1]
    return {"src_word": src, "src_len": np.full((bs, 1), seq, "int64"),
            "trg_word": trg, "trg_len": np.full((bs, 1), seq, "int64"),
            "label": src}


def test_slot_decoder_staggered_admissions_match_dedicated_decode():
    """Sequences admitted into the slot pool MID-FLIGHT (fewer slots
    than sequences, ragged source lengths) produce exactly the tokens
    the dedicated full-prefix greedy decoder produces — the continuous-
    batching decode is numerically invisible."""
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.generation import (
        NoFreeSlotError,
        SlotDecodeSession,
    )

    vocab, seq, D = 24, 8, 32
    cfg = dict(src_vocab_size=vocab, trg_vocab_size=vocab,
               max_length=seq, n_layer=1, n_head=2, d_model=D,
               d_inner=64)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 21
    startup.random_seed = 21
    with fluid.program_guard(main, startup):
        loss, feeds, extras = transformer.build(
            dropout=0.0, label_smooth_eps=0.0, **cfg)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    infer_prog = transformer.build_inference(main, extras["logits"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(22)
    for _ in range(50):
        exe.run(main, feed=_copy_task_batch(rng, 16, seq, vocab),
                fetch_list=[loss])

    src = rng.randint(3, vocab, (5, seq)).astype("int64")
    src_len = np.asarray([[seq], [seq - 3], [seq - 1], [2], [seq]],
                         "int64")
    want = transformer.greedy_generate(
        exe, infer_prog, extras["logits"].name, src, src_len, seq)

    sess = SlotDecodeSession(exe, num_slots=3, max_length=seq,
                             d_model=D, src_vocab_size=vocab,
                             trg_vocab_size=vocab, n_layer=1, n_head=2,
                             d_inner=64)
    # hand-staggered: fill the pool, step, admit into freed slots
    got = np.zeros_like(want)
    owner = {sess.admit(src[i], src_len[i]): i for i in range(3)}
    with pytest.raises(NoFreeSlotError):
        sess.admit(src[3], src_len[3])
    pending = [3, 4]
    steps = 0
    while owner or pending:
        while pending and sess.free_slots:
            i = pending.pop(0)
            owner[sess.admit(src[i], src_len[i])] = i
        for slot, tokens in sess.step().items():
            got[owner.pop(slot)] = tokens
        steps += 1
        assert steps < 100
    np.testing.assert_array_equal(got, want)

    # one executable for every step dispatch regardless of occupancy:
    # the step program's shapes never changed, so a second full batch
    # through sess.generate adds no fresh compiles
    before = exec_cache.stats()["fresh_compiles"]
    again = sess.generate(src, src_len)
    np.testing.assert_array_equal(again, want)
    assert exec_cache.stats()["fresh_compiles"] == before


def test_server_metrics_exported(demo_predictor):
    """The SLO series land in the process registry scrape."""
    from paddle_tpu.observability import REGISTRY

    server = BatchingServer(demo_predictor, max_batch=4)
    try:
        server.run({"x": np.zeros((3, 12), "float32")})
        with pytest.raises(DeadlineExceededError):
            # a zero deadline always lapses before delivery
            server.submit({"x": np.zeros((1, 12), "float32")},
                          deadline_s=0.0).result(timeout=30)
        text = REGISTRY.to_prometheus()
        assert 'paddle_tpu_serving_requests_total{outcome="ok"}' in text
        assert "paddle_tpu_serving_request_seconds_bucket" in text
        assert "paddle_tpu_serving_batch_occupancy_count" in text
        assert "paddle_tpu_serving_queue_depth" in text
        assert 'paddle_tpu_serving_requests_total{outcome="deadline"}' \
            in text
    finally:
        server.close()
