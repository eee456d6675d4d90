"""Network front end: the socket serving plane (serving/frontend.py +
serving/client.py) and the JSON-lines substrate extensions underneath
it (distributed/master.py streaming + connection callbacks).

Covers, in order: the substrate regression surface (dict dispatch,
MasterService and FleetCoordinator behavior UNCHANGED under the
extended serve_json_lines), the wire codec (bit-exact arrays, typed
error round trips), unary predict (parity, deadlines, degradation),
streaming generate (incremental chunks, best-of-N + prefix reuse over
the wire, oracle parity), disconnect-safe reclamation (kill/cancel a
client mid-stream -> slot + page refcounts back to conservation),
the net.* chaos sites with classified-retry coverage (severed
connections are retried or surface typed errors — never a hang), and
the SIGTERM composition with DecodeSnapshotManager (subprocess leg:
the frontend banks its backlog and dies by the signal).
"""

import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.distributed.master import (
    JsonLineClient,
    MasterClient,
    MasterService,
    close_json_server,
    serve_json_lines,
)
from paddle_tpu.executor import global_scope
from paddle_tpu.observability import lock_witness
from paddle_tpu.resilience import chaos
from paddle_tpu.serving.client import (
    ServingClient,
    StreamBrokenError,
    decode_array,
    encode_array,
    error_from_wire,
    error_to_wire,
)
from paddle_tpu.serving.degradation import DegradedError
from paddle_tpu.serving.frontend import ServingFrontend
from paddle_tpu.serving.generation import (
    NoFreePageError,
    NoFreeSlotError,
    Sampler,
    SlotDecodeSession,
)
from paddle_tpu.serving.server import (
    BatchingServer,
    DeadlineExceededError,
    QueueFullError,
    ServerClosedError,
    ServingError,
)

VOCAB, SEQ, D, S = 24, 8, 32, 4
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=1,
           n_head=2, d_inner=64)


@pytest.fixture(autouse=True)
def _clean_chaos_and_flags():
    yield
    chaos.disable()
    flags.set_flag("dispatch_retries", 0)


# ---------------------------------------------------------------------------
# substrate: serve_json_lines extensions + regression
# ---------------------------------------------------------------------------

def test_substrate_dict_dispatch_unchanged():
    """The legacy one-request/one-response contract (and the legacy
    dispatch signature) is untouched: MasterService serves its whole
    task protocol through the extended substrate."""
    svc = MasterService(chunks_per_task=1, timeout_s=5.0)
    addr = svc.serve()
    try:
        client = MasterClient(addr)
        client.set_dataset(["a", "b"])
        t1 = client.get_task()
        assert t1 is not None and t1.chunks in (["a"], ["b"])
        assert client.task_finished(t1.task_id)
        st = client.status()
        assert st["done"] == 1 and st["todo"] == 1
        client.close()
    finally:
        svc.close()


def test_substrate_streaming_callbacks_and_byte_accounting():
    opened, closed = [], []

    def dispatch(req, conn):
        assert conn.id >= 1
        if req["m"] == "one":
            conn.state["seen"] = True
            return {"ok": True, "x": req["x"]}

        def gen():
            for i in range(3):
                yield {"ok": True, "i": i}
            yield {"ok": True, "event": "end"}

        return gen()

    srv, addr = serve_json_lines(
        dispatch, pass_conn=True,
        on_open=lambda c: opened.append(c.id),
        on_close=lambda c: closed.append((c.id, c.state.get("seen"))))
    try:
        cl = JsonLineClient(addr)
        assert cl._call(m="one", x=7) == {"ok": True, "x": 7}
        cl._send_line({"m": "stream"})
        msgs = [cl._recv_line() for _ in range(4)]
        assert [m.get("i") for m in msgs[:3]] == [0, 1, 2]
        assert msgs[3]["event"] == "end"
        cl.close()
        deadline = time.monotonic() + 5.0
        while not closed and time.monotonic() < deadline:
            time.sleep(0.01)
        assert opened == [1] and closed == [(1, True)]
        with srv._conn_mu:
            assert srv.bytes_sent > 0 and srv.bytes_received > 0
    finally:
        close_json_server(srv)


def test_substrate_stream_exception_becomes_terminal_error_line():
    cleaned = []

    def dispatch(req):
        def gen():
            try:
                yield {"ok": True, "i": 0}
                raise RuntimeError("mid-stream boom")
            finally:
                cleaned.append(True)

        return gen()

    srv, addr = serve_json_lines(dispatch)
    try:
        cl = JsonLineClient(addr)
        cl._send_line({})
        assert cl._recv_line() == {"ok": True, "i": 0}
        err = cl._recv_line()
        assert err["ok"] is False and "mid-stream boom" in err["error"]
        cl.close()
        assert cleaned == [True]
    finally:
        close_json_server(srv)


def test_fleet_coordinator_behavior_unchanged():
    """The elastic coordinator (the substrate's other production user)
    still registers/heartbeats/deregisters identically."""
    from paddle_tpu.elastic.coordinator import FleetClient, FleetCoordinator

    co = FleetCoordinator(lease_s=2.0, min_workers=1)
    addr = co.serve()
    try:
        fc = FleetClient(addr)
        view = fc.register(worker_id="w0")
        assert (view["world"], view["rank"]) == (1, 0)
        hb = fc.heartbeat("w0")
        assert hb["generation"] == view["generation"]
        assert fc.leave("w0")
        fc.close()
    finally:
        co.close()


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

def test_array_codec_bit_exact():
    nan_payload = np.array([1.0, np.float32(np.nan), -np.inf, 3e-41],
                           dtype="float32").reshape(2, 2)
    for arr in (nan_payload,
                np.arange(12, dtype="int64").reshape(3, 4),
                np.asarray(2.5, dtype="float64")):
        back = decode_array(encode_array(arr))
        assert back.dtype == arr.dtype and back.shape == arr.shape
        assert np.array_equal(arr.tobytes(), back.tobytes())
        back[...] = 0  # decoded arrays must be writable


def test_typed_errors_round_trip_the_wire():
    for exc in (QueueFullError("q"), DeadlineExceededError("d"),
                ServerClosedError("c"), NoFreeSlotError("s"),
                NoFreePageError("p"), StreamBrokenError("b")):
        back = error_from_wire(error_to_wire(exc))
        assert type(back) is type(exc) and str(exc) in str(back)
    deg = error_from_wire(error_to_wire(
        DegradedError("shed", state="shed", retry_after_s=0.25)))
    assert isinstance(deg, DegradedError)
    assert deg.state == "shed" and deg.retry_after_s == 0.25
    from paddle_tpu.resilience.retry import is_transient

    assert is_transient(deg), "wire DegradedError lost retriability"
    unknown = error_from_wire({"ok": False, "etype": "Weird",
                               "error": "x"})
    assert isinstance(unknown, ServingError) and "Weird" in str(unknown)


# ---------------------------------------------------------------------------
# fixtures: demo predictor (unary) + trained decoder (streaming)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def demo_predictor(tmp_path_factory):
    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    from paddle_tpu.serving import loadgen

    model_dir = str(tmp_path_factory.mktemp("fe_demo") / "model")
    loadgen.build_demo_model(model_dir, train_steps=5)
    return create_paddle_predictor(
        NativeConfig(model_dir=model_dir, use_tpu=False))


@pytest.fixture(scope="module")
def trained():
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 41
    startup.random_seed = 41
    scope = global_scope()
    with fluid.program_guard(main, startup):
        transformer.build(dropout=0.0, label_smooth_eps=0.0,
                          max_length=SEQ, d_model=D, **CFG)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(7)
    src = rng.randint(3, VOCAB, (8, SEQ)).astype("int64")
    return {"exe": exe, "scope": scope, "src": src}


def _paged(trained, **kw):
    args = dict(num_slots=S, max_length=SEQ, d_model=D, paged=True,
                page_size=4, steps=2, num_groups=2,
                prefix_cache_pages=8,
                sampler=Sampler(strategy="top_k", top_k=4,
                                temperature=0.9, seed=11),
                scope=trained["scope"].new_scope())
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


def _drained(sess, timeout=60.0):
    """Wait until every teardown landed: every slot free, no queued
    request, pool at conservation. The free-slot check matters: a
    mid-admission window (request popped, slot popped, dispatch in
    flight) satisfies the weaker live/pending/conservation predicate —
    disconnect reclamation is processed on the decode worker and tests
    must wait for it, not race it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if (not sess.active_slots and not sess.pending_requests
                and sess.free_slots == sess._S
                and sess.pool_conserved):
            return True
        time.sleep(0.02)
    return False


# ---------------------------------------------------------------------------
# unary predict over the wire
# ---------------------------------------------------------------------------

def test_predict_bit_exact_parity(demo_predictor):
    from paddle_tpu.serving import loadgen

    server = BatchingServer(demo_predictor, max_batch=8, workers=1,
                            batch_linger_s=0.002)
    with server, ServingFrontend(server=server) as fe:
        cl = ServingClient(fe.address)
        for req in loadgen.demo_requests(6, seed=5):
            got = cl.predict(req)
            want = server.run_reference(req)
            assert all(np.array_equal(g, w) for g, w in zip(got, want))
        # list-form inputs (feed order) work too
        req = loadgen.demo_requests(1, seed=9)[0]
        got = cl.predict([req["x"]])
        want = server.run_reference(req)
        assert all(np.array_equal(g, w) for g, w in zip(got, want))
        cl.close()


def test_predict_deadline_maps_to_typed_error(demo_predictor):
    server = BatchingServer(demo_predictor, max_batch=8, workers=1,
                            batch_linger_s=0.2)
    with server, ServingFrontend(server=server) as fe:
        cl = ServingClient(fe.address)
        with pytest.raises(DeadlineExceededError):
            cl.predict({"x": np.zeros((2, 12), dtype="float32")},
                       deadline_s=1e-6)
        cl.close()


def test_predict_shed_reaches_client_typed_then_retries_through(
        demo_predictor):
    server = BatchingServer(
        demo_predictor, max_batch=8, workers=1, max_queue_depth=4,
        batch_linger_s=0.05,
        degradation=dict(brownout_at=0.25, shed_at=0.5,
                         recover_at=0.25, retry_after_s=0.05))
    with server, ServingFrontend(server=server) as fe:
        req = {"x": np.zeros((1, 12), dtype="float32")}

        def flood(n):
            rejects, okays = [], []

            def one():
                cl = ServingClient(fe.address)
                try:
                    cl.predict(req)
                    okays.append(1)
                except DegradedError as exc:
                    rejects.append(exc)
                finally:
                    cl.close()

            threads = [threading.Thread(target=one) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            return rejects, okays

        # no retries: the typed reject surfaces to the caller
        rejects, okays = flood(16)
        assert rejects, "the flood never tripped shed"
        assert okays, "shed refused everything, including the drain"
        assert all(isinstance(e, DegradedError)
                   and e.retry_after_s > 0 for e in rejects)
        # with the classified budget armed, the SAME flood rides the
        # retry-after hint through the drain instead of surfacing
        flags.set_flag("dispatch_retries", 8)
        rejects, okays = flood(16)
        assert not rejects and len(okays) == 16


def test_unknown_method_is_typed(demo_predictor):
    server = BatchingServer(demo_predictor, max_batch=8, workers=1)
    with server, ServingFrontend(server=server) as fe:
        cl = ServingClient(fe.address)
        with pytest.raises(ServingError, match="unknown method"):
            cl._request(method="nope")
        # a predict-only frontend refuses generate with a typed error
        with pytest.raises(ServingError, match="no decode session"):
            list(cl.generate(np.zeros(SEQ, dtype="int64")))
        cl.close()


# ---------------------------------------------------------------------------
# streaming generate
# ---------------------------------------------------------------------------

def test_generate_streams_incrementally_and_matches_oracle(trained):
    src = trained["src"]
    sess, oracle = _paged(trained), _paged(trained)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        events = list(cl.generate(src[0], src_len=SEQ))
        kinds = [e["event"] for e in events]
        assert kinds[0] == "queued" and kinds[-1] == "end"
        token_events = [e for e in events if e["event"] == "tokens"]
        # SEQ=8, steps=2: the stream must arrive in PER-DISPATCH
        # chunks, not one end-of-generation lump
        assert len(token_events) >= 2
        assert all(len(e["tokens"]) <= 2 for e in token_events)
        wire = cl.generate_full(src[1], src_len=5)
        cl.close()
    want0 = oracle.generate(src[0][None, :], [SEQ])
    want1 = oracle.generate(src[1][None, :], [5])
    row0 = np.full(SEQ, 2, dtype="int64")
    row0[0] = 1
    fill = 1
    for e in token_events:
        row0[fill:fill + len(e["tokens"])] = e["tokens"]
        fill += len(e["tokens"])
    assert np.array_equal(row0, want0[0])
    assert np.array_equal(wire[0], want1[0])


@pytest.fixture(params=[False, True], ids=["plain", "lock_witness"])
def witness(request):
    """Armed BEFORE the session and the frontend are built (the witness
    wraps a framework lock at its construction), the lock witness must
    see a real frontend serve its streams with no lock-order cycle and
    no lock held across a device dispatch."""
    if not request.param:
        yield
        return
    lock_witness.enable()
    lock_witness.reset()
    try:
        yield
        report = lock_witness.report()
    finally:
        lock_witness.disable()
        lock_witness.reset()
    assert report["registered"] and not report["degraded"], report
    assert not report["cycles"], report["cycles"]
    assert not report["long_holds"], report["long_holds"]


def test_generate_best_of_and_prefix_reuse_over_the_wire(trained, witness):
    src = trained["src"]
    pfx = [int(t) for t in src[0][:5]]
    sess, oracle = _paged(trained), _paged(trained)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        wire = cl.generate_full(src[0], src_len=SEQ, n=2,
                                prefix_tokens=pfx)
        # the same forced prefix again: served from the prefix cache
        wire2 = cl.generate_full(src[0], src_len=SEQ, n=2,
                                 prefix_tokens=pfx)
        stats = sess.prefix_cache_stats()
        cl.close()
    want = oracle.generate_best_of(src[0], 2, src_len=SEQ,
                                   prefix_tokens=pfx)
    want2 = oracle.generate_best_of(src[0], 2, src_len=SEQ,
                                    prefix_tokens=pfx)
    assert np.array_equal(wire, want)
    assert np.array_equal(wire2, want2)
    assert stats["lookups"] >= 2 and stats["hits"] >= 1, stats


def test_generate_beam_over_the_wire_matches_in_process(trained):
    """Beam socket parity (PR 15): the wire grammar — ``admitted`` with
    beam metadata, one ``beam`` survivor chunk per dispatch, a final
    ``beam_end`` n-best — reassembles bit-identical to the in-process
    ``generate_beam``, the client's incremental replay cross-checks the
    chunks against the n-best, and a disconnected beam stream returns
    every lane slot to conservation."""
    src = trained["src"]
    args = dict(num_slots=S, max_length=SEQ, d_model=D, paged=True,
                page_size=4, beam_width=2,
                scope=trained["scope"].new_scope())
    args.update(CFG)
    sess = SlotDecodeSession(trained["exe"], **args)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        events = []
        got_t, got_s = cl.generate_beam(src[0], src_len=SEQ,
                                        on_event=events.append)
        kinds = [e["event"] for e in events]
        assert kinds[0] == "admitted" and kinds[-2:] == ["beam_end",
                                                         "end"]
        adm = events[0]
        assert adm["beam_width"] == 2 and len(adm["slots"]) == 2
        # one survivor chunk PER DISPATCH (parents + tokens + scores),
        # not an end-of-beam lump
        beam_events = [e for e in events if e["event"] == "beam"]
        assert len(beam_events) >= 3
        assert all(len(e["parents"]) == 2 and len(e["tokens"]) == 2
                   for e in beam_events)
        # beam=True composes with nothing: n > 1 is a typed reject
        with pytest.raises(ServingError):
            list(cl.generate(src[0], src_len=SEQ, n=2, beam=True))
        # disconnect mid-beam: the whole lane reclaims
        gen = cl.generate(src[1], src_len=SEQ, beam=True)
        assert next(gen)["event"] == "admitted"
        cl.close()  # severed socket: the close hook cancels the beam
    assert _drained(sess)
    assert sess.free_beams == S // 2 and sess.pool_conserved
    # wire parity: the frontend is closed, the session is drained — the
    # SAME session decoding the SAME source in-process must reproduce
    # the wire n-best bit-for-bit (the greedy lattice is deterministic)
    want_t, want_s = sess.generate_beam(src[0], SEQ)
    np.testing.assert_array_equal(got_t, want_t)
    np.testing.assert_array_equal(got_s, want_s)
    # a beam that finishes with NO attached stream (the
    # preemption-orphan shape) banks its n-best under the claim id —
    # and the wire take_result reaches the beam bank
    lane = sess.admit_beam(src[1], SEQ)
    rid = sess.register_beam_owner(lane)
    while lane in sess.active_beams:
        sess.step()
    with ServingFrontend(session=sess) as fe2:
        cl2 = ServingClient(fe2.address)
        bt, bs = cl2.take_result(rid)
        cl2.close()
    assert sess.take_beam_result(rid) is None  # claimed over the wire
    np.testing.assert_array_equal(bt, sess.generate_beam(src[1], SEQ)[0])
    assert bs.shape == (2,)


def test_beam_len_penalty_rescoring_wire_matches_in_process(trained):
    """GNMT length-penalty rescoring as a wire option: ``len_penalty``
    on a beam request makes the frontend rescore the final n-best
    (``beam_end`` reorders under the penalized scores and carries the
    ``order`` permutation the client replay-check realigns through);
    the wire result is bit-identical to the in-process
    ``generate_beam(len_penalty=...)``, which itself is exactly
    ``gnmt_rescore_nbest`` over the raw n-best. ``len_penalty``
    without ``beam`` is a typed reject."""
    from paddle_tpu.models.transformer import gnmt_rescore_nbest

    src = trained["src"]
    args = dict(num_slots=S, max_length=SEQ, d_model=D, paged=True,
                page_size=4, beam_width=2,
                scope=trained["scope"].new_scope())
    args.update(CFG)
    sess = SlotDecodeSession(trained["exe"], **args)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        events = []
        got_t, got_s = cl.generate_beam(src[0], src_len=SEQ,
                                        len_penalty=2.0,
                                        on_event=events.append)
        end = [e for e in events if e["event"] == "beam_end"][0]
        assert end["len_penalty"] == 2.0
        assert sorted(end["order"]) == [0, 1]
        with pytest.raises(ServingError, match="beam=true"):
            list(cl.generate(src[0], src_len=SEQ, len_penalty=0.6))
        cl.close()
    assert _drained(sess)
    want_t, want_s = sess.generate_beam(src[0], SEQ, len_penalty=2.0)
    np.testing.assert_array_equal(got_t, want_t)
    np.testing.assert_array_equal(got_s, want_s)
    # the in-process rescoring IS gnmt_rescore_nbest over the raw
    # n-best (penalized scores, score-descending reorder)
    raw_t, raw_s = sess.generate_beam(src[0], SEQ)
    order, re_t, re_s = gnmt_rescore_nbest(raw_t, raw_s, sess._eos, 2.0)
    np.testing.assert_array_equal(re_t, want_t)
    np.testing.assert_array_equal(re_s, want_s)
    assert sorted(int(i) for i in order) == [0, 1]
    # len_penalty = 0 divides by 1: identity order, raw scores
    z_t, z_s = sess.generate_beam(src[0], SEQ, len_penalty=0.0)
    np.testing.assert_array_equal(z_t, raw_t)
    np.testing.assert_allclose(z_s, raw_s, rtol=1e-6)


def test_generate_backlog_exceeding_slots_completes_concurrently(
        trained):
    """6 concurrent wire streams over a 4-slot pool: the overflow rides
    the session's persistent queue; every stream completes and matches
    the greedy oracle (greedy decode is slot-independent, so the
    nondeterministic admission order cannot affect the bits)."""
    src = trained["src"]
    sess = _paged(trained, sampler=None, prefix_cache_pages=0)
    oracle = _paged(trained, sampler=None, prefix_cache_pages=0)
    results = {}
    errors = []
    with ServingFrontend(session=sess) as fe:

        def one(i):
            cl = ServingClient(fe.address)
            try:
                results[i] = cl.generate_full(src[i], src_len=SEQ)
            except Exception as exc:  # noqa: BLE001 - collected
                errors.append(exc)
            finally:
                cl.close()

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert _drained(sess)
    assert not errors, errors[:3]
    for i in range(6):
        want = oracle.generate(src[i][None, :], [SEQ])
        assert np.array_equal(results[i][0], want[0]), "row %d" % i


def test_a_lost_admission_batch_fails_each_of_its_streams_typed(trained):
    """Three wire streams queued together are ONE admission batch; its
    dispatch fails past the retry budget (a ``serve.admit`` fault, no
    retries), the batch is rolled back whole, and EACH of the three
    streams is shown the typed error. The worker lives on: the next
    request serves, and the pool is conserved."""
    src = trained["src"]
    sess = _paged(trained, sampler=None, prefix_cache_pages=0,
                  num_groups=S)
    admit_pending = sess.admit_pending

    def once_three_are_queued():
        # hold admission back until the three streams are in the queue,
        # so that they form one batch
        if chaos.ENABLED and len(sess.pending_requests) < 3:
            return {}
        return admit_pending()

    sess.admit_pending = once_three_are_queued
    errors, results = {}, {}
    with ServingFrontend(session=sess) as fe:

        def one(i):
            cl = ServingClient(fe.address)
            try:
                results[i] = cl.generate_full(src[i], src_len=SEQ)
            except ServingError as exc:
                errors[i] = exc
            finally:
                cl.close()

        chaos.configure("io@site=serve.admit,n=1")
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert chaos.fires("serve.admit") == 1
        chaos.disable()
        assert sorted(errors) == [0, 1, 2] and not results
        for exc in errors.values():
            assert "chaos" in str(exc)
        assert sess.admit_dispatches == 0 and _drained(sess)
        cl = ServingClient(fe.address)
        assert cl.generate_full(src[3], src_len=SEQ).shape == (1, SEQ)
        cl.close()
        assert _drained(sess)


def test_client_disconnect_mid_stream_reclaims_pool(trained):
    src = trained["src"]
    sess = _paged(trained)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        # warm the admit/step executables first: the disconnect scenario
        # must race the decode loop, not a cold XLA compile
        cl.generate_full(src[0], src_len=SEQ)
        gen = cl.generate(src[2], src_len=SEQ)
        next(gen)
        # hard kill: close the socket without a cancel line — only the
        # substrate's close callback can reclaim
        cl.close()
        assert _drained(sess), (
            "disconnect did not reclaim: live=%r pending=%r "
            "conserved=%r" % (sess.active_slots,
                              sess.pending_requests,
                              sess.pool_conserved))
        assert sess.free_slots == S
        assert sess.free_pages == sess._P - 1 - sess.cached_pages
        # a subsequent admission over a fresh connection succeeds
        cl2 = ServingClient(fe.address)
        out = cl2.generate_full(src[2], src_len=SEQ)
        assert out.shape == (1, SEQ)
        cl2.close()


def test_inband_cancel_reclaims_and_connection_stays_usable(trained):
    src = trained["src"]
    sess, oracle = _paged(trained), _paged(trained)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        gen = cl.generate(src[3], src_len=SEQ)
        next(gen)
        gen.close()  # sends the in-band cancel, drains the ack
        assert _drained(sess)
        assert sess.pool_conserved and sess.free_slots == S
        # the SAME connection serves the next request
        wire = cl.generate_full(src[4], src_len=SEQ)
        cl.close()
    # drive the oracle through the same effective history (a cancelled
    # generation admits and releases; slot order is preserved)
    o = _oracle_after_cancel(oracle, src)
    assert np.array_equal(wire[0], o[0])


def _cancel_in_one_pass(sess, specs):
    """Behind a bare decode worker: admit one stream a spec, hold the
    worker inside its step dispatch while every stream is cancelled, let
    it go, and wait for the teardown. The streams' first events."""
    from paddle_tpu.serving.frontend import _DecodeWorker, _Stream

    gate = threading.Event()
    step = sess.step
    sess.step = lambda: (gate.wait(30), step())[1]
    worker = _DecodeWorker(sess)
    try:
        streams = [_Stream(dict(spec)) for spec in specs]
        for stream in streams:
            worker.submit(stream)
        first = [[stream.q.get(timeout=60) for _ in range(2)]
                 for stream in streams]            # queued, admitted
        assert sorted(sess.active_slots) == list(range(len(specs)))
        # the worker is inside its step: the cancels wait for one pass
        for stream in streams:
            worker.cancel(stream)
        gate.set()
        assert _drained(sess)
        assert all(stream.done for stream in streams)
    finally:
        gate.set()
        worker.stop(drain=False, timeout=30)
    return first


def test_a_pass_with_n_cancelled_streams_makes_one_table_dispatch(trained):
    """Three streams cancelled while the worker steps are torn down by its
    next pass together: ONE ``cancel_many``, one table dispatch of three
    rows, the slots back in cancel order, the pool conserved."""
    sess = _paged(trained, sampler=None, prefix_cache_pages=0,
                  num_groups=S)
    calls = []
    cancel_many = sess.cancel_many
    sess.cancel_many = lambda slots: (calls.append(list(slots)),
                                      cancel_many(slots))[1]
    specs = [{"src": trained["src"][i], "src_len": SEQ, "n": 1,
              "prefix": None} for i in range(3)]
    first = _cancel_in_one_pass(sess, specs)
    assert [ev[1]["event"] for ev in first] == ["admitted"] * 3
    assert calls == [[0, 1, 2]]
    assert (sess.release_dispatches, sess.release_rows) == (1, 3)
    assert sess._free[-3:] == [0, 1, 2] and sess.pool_conserved


def test_a_decoder_only_session_behind_the_worker_cancels_with_none():
    """The same worker, the same call, a session whose cancel is host
    bookkeeping: ``cancel_many`` is its ``cancel`` slot by slot and no
    executor call is made for it."""
    from test_latent_moe_decoder import make_session, prompts_of

    sess, _tree = make_session(max_new_tokens=400)
    runs = []
    exe = sess._exe

    class _Counted(object):
        """Between the session and its executor: counts the calls."""

        def __getattr__(self, name):
            call = getattr(exe, name)
            if name not in ("run", "run_multi_step"):
                return call
            return lambda *a, **kw: (runs.append(name), call(*a, **kw))[1]

    sess._exe = _Counted()
    calls = []
    cancel_many = sess.cancel_many

    def counted_cancel_many(slots):
        before = len(runs)
        out = cancel_many(slots)
        calls.append((list(slots), len(runs) - before))
        return out

    sess.cancel_many = counted_cancel_many
    specs = []
    for prompt in prompts_of([7, 21, 12], seed=4):
        src = np.zeros(32, "int64")
        src[:len(prompt)] = prompt
        specs.append({"src": src, "src_len": len(prompt), "n": 1,
                      "prefix": None})
    _cancel_in_one_pass(sess, specs)
    # (the session fills slots bucket by bucket, so not in stream order)
    assert [(sorted(slots), n) for slots, n in calls] == [([0, 1, 2], 0)]
    assert sess.pool_conserved and sess.pages_in_use == 0


def _until(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


# ---------------------------------------------------------------------------
# the outbox: while requests wait for a slot, a dispatch's events ride the
# next launch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=["paged", "decoder_only"])
def served(request, trained):
    """``(session, spec)`` of each kind of session the worker drives, four
    slots and at most four decode dispatches a request; ``spec(i)`` is
    request i's, over eight prompts."""
    if request.param == "paged":
        sess = _paged(trained, sampler=None, prefix_cache_pages=0,
                      num_groups=S)
        prompts = [(row, SEQ) for row in trained["src"]]
    else:
        from test_latent_moe_decoder import make_session, prompts_of

        sess, _tree = make_session(max_new_tokens=8)
        prompts = []
        for prompt in prompts_of([7, 21, 12, 5, 9, 30, 16, 3], seed=4):
            src = np.zeros(32, "int64")
            src[:len(prompt)] = prompt
            prompts.append((src, len(prompt)))

    def spec(i):
        src, n = prompts[i % len(prompts)]
        return {"src": src, "src_len": n, "n": 1, "prefix": None}

    return sess, spec


class _LoggedQueue(queue.SimpleQueue):
    def __init__(self, log, index):
        queue.SimpleQueue.__init__(self)
        self._log, self._index = log, index

    def put(self, item, *a, **kw):
        self._log.append(("put", self._index, item))
        queue.SimpleQueue.put(self, item, *a, **kw)


class _Outboxed(object):
    """A bare decode worker over ``sess`` with its thread's doings in ONE
    log: every decode dispatch's ``launch`` and ``collect`` (this object
    stands between the session and its executor and wraps the
    ``in_flight`` it is handed; ``collect`` says whether requests waited
    for a slot then), the events the worker ``built`` from a dispatch
    (read off its outbox as ``_hand_off`` returns) and every ``put`` to a
    stream's queue. ``hook=False`` drops the ``in_flight``: a session
    whose step never calls it. ``lockstep=True`` makes every
    ``session.step()`` wait for ``allow()``."""

    def __init__(self, sess, spec, hook=True, lockstep=False):
        from paddle_tpu.serving.frontend import _DecodeWorker, _Stream

        self.log, self.streams = [], []
        self.sess, self.spec, self.hook = sess, spec, hook
        self._make_stream = _Stream
        self.exe, self.dispatches = sess._exe, 0
        self.fail_next = False
        self._sem = threading.Semaphore(0) if lockstep else None
        self._step = sess.step
        sess._exe, sess.step = self, self._gated_step
        self.worker = _DecodeWorker(sess)
        hand_off = self.worker._hand_off

        def logged_hand_off(finished):
            before = len(self.worker._outbox)
            out = hand_off(finished)
            self.log.append(("built", self.dispatches,
                             [ev for _st, ev in
                              list(self.worker._outbox)[before:]]))
            return out

        self.worker._hand_off = logged_hand_off

    # -- between the session and its executor --------------------------------
    def __getattr__(self, name):
        return getattr(self.exe, name)

    def run_multi_step(self, *a, **kw):
        self.dispatches += 1
        k = self.dispatches
        fn = kw.pop("in_flight", None)
        if self.hook and fn is not None:
            def launched():
                self.log.append(("launch", k))
                fn()

            kw["in_flight"] = launched
        else:
            self.log.append(("launch", k))
        out = self.exe.run_multi_step(*a, **kw)
        self.log.append(("collect", k, bool(self.sess.pending_requests)))
        return out

    def _gated_step(self):
        if self._sem is not None:
            assert self._sem.acquire(timeout=60)
        if self.fail_next:
            self.fail_next = False
            raise RuntimeError("the decode dispatch is lost")
        return self._step()

    # -- the test's side -----------------------------------------------------
    def allow(self, steps=1):
        for _ in range(steps):
            self._sem.release()

    def submit(self, n):
        for _ in range(n):
            stream = self._make_stream(self.spec(len(self.streams)))
            stream.q = _LoggedQueue(self.log, len(self.streams))
            self.streams.append(stream)
            self.worker.submit(stream)

    def built(self, k):
        """The events built from dispatch ``k``, once they are."""
        assert _until(lambda: any(e[:2] == ("built", k) for e in self.log))
        return [e[2] for e in self.log if e[:2] == ("built", k)][0]

    def where_put(self):
        """{id(event): where it was put}: ``("flight", k)`` between
        dispatch k's launch and its collect, ``("line", k)`` after
        dispatch k's collect and before the next launch."""
        at, out = ("line", 0), {}
        for entry in list(self.log):
            if entry[0] == "launch":
                at = ("flight", entry[1])
            elif entry[0] == "collect":
                at = ("line", entry[1])
            elif entry[0] == "put":
                out[id(entry[2])] = at
        return out

    def events(self, i):
        """Everything stream ``i`` was sent, in the order it was put."""
        return [e[2] for e in list(self.log) if e[:2] == ("put", i)]

    def close(self):
        if self._sem is not None:
            self.allow(1000)
        try:
            self.worker.stop(drain=False, timeout=30)
            assert not self.worker._thread.is_alive()
        finally:
            self.sess._exe, self.sess.step = self.exe, self._step
            del self.sess.step      # the class's own again
        assert _drained(self.sess)


def _assert_stream_order(events, whole):
    """``queued``, ``admitted``, ``tokens`` with ``seq`` contiguous from
    the admission's ``pos``, then ``end``; an error line may end it
    anywhere, and nothing follows either. ``whole``: the stream was
    neither cancelled nor failed, so all of that. The tokens it was
    sent."""
    names = [ev.get("event") if ev.get("ok") else "error" for ev in events]
    tokens, body = [], list(zip(names, events))
    if body and body[0][0] == "queued":
        body.pop(0)
    if not body or names[-1] == "error" and len(body) == 1:
        assert not whole    # cancelled or failed while still queued
        return tokens
    assert body[0][0] == "admitted", names
    seq = body[0][1]["pos"] + 1
    for at, (name, ev) in enumerate(body[1:], 1):
        if name == "tokens":
            assert ev["seq"] == seq, (names, ev, seq)
            seq += len(ev["tokens"])
            tokens += ev["tokens"]
        else:
            assert name in ("end", "error") and at == len(body) - 1, names
    if whole:
        assert names[-1] == "end" and tokens, names
    return tokens


def test_with_a_backlog_a_dispatch_s_events_ride_the_next_launch(served):
    """Ten requests over four slots, the worker a step at a time. A
    dispatch collected while requests wait for a slot has its events put
    AFTER the next dispatch's launch and BEFORE its collect; once nothing
    waits they are put as before, ahead of the next launch."""
    sess, spec = served
    box = _Outboxed(sess, spec, lockstep=True)
    try:
        box.submit(10)
        box.allow()
        box.built(1)       # the rest are enqueued by the pass after it
        box.allow(1000)
        assert _until(lambda: all(st.done for st in box.streams), 120)
        assert _until(lambda: not box.worker._outbox)
    finally:
        box.close()
    where = box.where_put()
    backlog = {e[1]: e[2] for e in box.log if e[0] == "collect"}
    held = at_once = 0
    for entry in box.log:
        if entry[0] != "built" or not entry[2]:
            continue
        k = entry[1]
        want = ("flight", k + 1) if backlog[k] else ("line", k)
        assert {where[id(ev)] for ev in entry[2]} == {want}, (k, backlog)
        held += backlog[k]
        at_once += not backlog[k]
    assert held >= 2 and at_once >= 1, backlog
    for i in range(10):
        _assert_stream_order(box.events(i), whole=True)


@pytest.mark.parametrize("hook", [True, False], ids=["hook", "no_hook"])
def test_no_stream_is_sent_its_events_out_of_order(served, hook):
    """56 requests over four slots with the worker running free, every
    seventh cancelled (queued or mid-flight): each stream is sent
    ``admitted``, contiguous ``seq`` and ``end`` in that order, streams of
    one prompt the same tokens. A session whose step never calls the hook
    loses and reorders nothing either: its events wait for ``step`` to
    return."""
    sess, spec = served
    box = _Outboxed(sess, spec, hook=hook)
    cancelled = set(range(3, 56, 7))
    try:
        box.submit(56)
        for i in sorted(cancelled):
            if i % 2:      # mid-flight: once it holds a slot
                _until(lambda: box.streams[i].live or box.streams[i].done)
            box.worker.cancel(box.streams[i])
        assert _until(lambda: all(st.done for st in box.streams), 240)
        assert _until(lambda: not box.worker._outbox)
    finally:
        box.close()
    by_prompt = {}
    for i in range(56):
        tokens = _assert_stream_order(box.events(i),
                                      whole=i not in cancelled)
        if i not in cancelled:
            by_prompt.setdefault(i % 8, []).append(tokens)
    assert all(t == same[0] for same in by_prompt.values() for t in same)
    flights = [at for at in box.where_put().values() if at[0] == "flight"]
    assert bool(flights) == hook


@pytest.mark.parametrize("how", ["op", "failure", "close"])
def test_nothing_is_left_in_the_outbox_behind(served, how):
    """With a dispatch's events held for the next launch: an op at its
    quiesce point, a failed dispatch's error lines and a
    ``close(drain=False)`` each find (or leave) the outbox empty, and the
    held events are put before whatever they send."""
    sess, spec = served
    box = _Outboxed(sess, spec, lockstep=True)
    worker, seen = box.worker, {}
    fail_tracked = worker._fail_tracked

    def logged_fail_tracked(exc):
        fail_tracked(exc)
        seen["failure"] = (len(worker._outbox), worker._hold)

    worker._fail_tracked = logged_fail_tracked
    try:
        box.submit(10)
        box.allow()
        box.built(1)
        box.allow()
        held = box.built(2)         # four slots live, six requests queued
        assert held and _until(lambda: worker._hold)
        # the worker stands before dispatch 3 with dispatch 2's events
        assert _until(lambda: len(worker._outbox) >= len(held))
        assert not set(map(id, held)) & set(box.where_put())
        if how == "op":
            def op():
                box.log.append(("op",))
                return len(worker._outbox), worker._hold

            got = []
            t = threading.Thread(
                target=lambda: got.append(worker.call(op)))
            t.start()
            assert _until(lambda: worker._ops)
            box.allow()             # dispatch 3: its events are held too
            t.join(timeout=60)
            assert got == [(0, False)]
            last = box.built(3)
            where = box.where_put()
            assert {where[id(ev)] for ev in held} == {("flight", 3)}
            assert {where[id(ev)] for ev in last} == {("line", 3)}
            log = list(box.log)
            assert max(n for n, e in enumerate(log) if e[0] == "put"
                       and any(e[2] is ev for ev in last)) < log.index(
                           ("op",))
        elif how == "failure":
            tracked = [i for i, st in enumerate(box.streams) if st.live]
            assert len(tracked) == 4
            box.fail_next = True
            box.allow()
            assert _until(lambda: "failure" in seen)
            assert seen["failure"] == (0, False)
            for i in tracked:
                events = box.events(i)
                assert "lost" in events[-1]["error"], events[-1]
                _assert_stream_order(events, whole=False)
            assert all(id(ev) in box.where_put() for ev in held)
        else:
            t = threading.Thread(
                target=lambda: worker.stop(drain=False, timeout=60))
            t.start()
            assert _until(lambda: worker._stop)
            box.allow()
            t.join(timeout=60)
            assert not worker._thread.is_alive() and not worker._outbox
            for i in range(10):
                events = box.events(i)
                assert not events[-1].get("ok"), events[-1]
                _assert_stream_order(events, whole=False)
            assert all(id(ev) in box.where_put() for ev in held)
    finally:
        box.close()


# ---------------------------------------------------------------------------
# the connection watcher: who reads a cancel or EOF while a stream is in flight
# ---------------------------------------------------------------------------


class _Wired(object):
    """The frontend's consume path on socket pairs, with no session: a real
    ``_ConnWatcher`` whose cancels are recorded, ``n`` connections as the
    substrate hands them to a dispatcher, one watched stream each. The
    test plays the decode worker (puts to a stream's queue), the client
    (writes to ``clients[i]``) and the handler (``next_event(i)``)."""

    def __init__(self, n=1, pause_s=0.05):
        from types import SimpleNamespace

        from paddle_tpu.serving.frontend import _ConnWatcher, _Stream

        self.cancelled = []
        self.fe = ServingFrontend.__new__(ServingFrontend)
        self.fe._decode = SimpleNamespace(cancel=self.cancelled.append)
        self.watcher = self.fe._watcher = _ConnWatcher(
            self.cancelled.append, pause_s=pause_s)
        self.conns, self.clients, self.streams = [], [], []
        for i in range(n):
            ours, theirs = socket.socketpair()
            self.conns.append(SimpleNamespace(
                id=i + 1, sock=ours, rfile=ours.makefile("rb")))
            self.clients.append(theirs)
            self.streams.append(_Stream({}))
            self.watcher.watch(self.conns[i], self.streams[i])

    def next_event(self, i=0):
        return self.fe._next_event(self.streams[i], self.conns[i])

    def close(self):
        self.watcher.close()
        for conn, theirs in zip(self.conns, self.clients):
            conn.rfile.close()
            conn.sock.close()
            theirs.close()


_CHUNK = {"ok": True, "event": "tokens", "tokens": [7]}
_END = {"ok": True, "event": "end"}
_ACK = {"ok": True, "event": "cancelled"}
_IDLE_ACK = {"ok": True, "event": "cancelled", "idle": True}
_CANCEL_LINE = b'{"method": "cancel"}\n'


def test_inband_cancel_is_read_while_the_queue_never_runs_empty():
    """A stream whose chunks come faster than its handler drains them (a
    decode round of 44 ms against the old poll cadence of 50) never finds
    its queue empty. The cancel is not the handler's to find any more:
    the watcher reads it as it arrives, cancels the stream on the decode
    worker and posts the verdict behind the chunks already queued, so it
    is answered then and not after the stream's last token."""
    w = _Wired()
    stop = threading.Event()

    def decode_worker():
        while not stop.is_set():
            if w.streams[0].q.qsize() < 2:
                w.streams[0].q.put(_CHUNK)
            time.sleep(0.002)

    feeder = threading.Thread(target=decode_worker)
    feeder.start()
    try:
        assert w.next_event() == ([_CHUNK], None)
        w.clients[0].sendall(_CANCEL_LINE)
        t0 = time.monotonic()
        lines, ended = w.next_event()
        while ended is None and time.monotonic() - t0 < 2.0:
            assert lines == [_CHUNK]  # the queue never ran empty
            time.sleep(0.005)
            lines, ended = w.next_event()
        assert (lines, ended) == ([_ACK], "cancelled")
        assert time.monotonic() - t0 < 0.5
        # cancelled on the worker by the watcher, taken out by the handler
        assert w.cancelled == [w.streams[0]]
        counts = w.watcher.counts()
        assert (counts["cancel"], counts["eof"]) == (1, 0)
        assert counts["watching"] == 0
        assert counts["handler_empty_wakeups"] == 0
        # (folded in as the handler took its connection out)
        assert counts["handler_wakeups"] == w.streams[0].wakeups > 1
    finally:
        stop.set()
        feeder.join()
        w.close()


def test_a_poll_on_the_callers_thread_posts_each_verdict_once():
    """``_ConnWatcher.poll()``, which the decode worker calls at the top
    of a pass while it holds events: the cancel lines readable NOW are
    read on the caller's thread, racing the watcher's own; each stream is
    cancelled on the decode worker once and handed one verdict, and a
    connection with nothing to read is left alone."""
    w = _Wired(n=8)
    try:
        for i in range(0, 8, 2):
            w.clients[i].sendall(_CANCEL_LINE)
        for _ in range(50):
            w.watcher.poll()
        want = [w.streams[i] for i in range(0, 8, 2)]
        assert _until(lambda: len(w.cancelled) >= 4)
        time.sleep(0.05)
        w.watcher.poll()
        assert sorted(map(id, w.cancelled)) == sorted(map(id, want))
        for i in range(8):
            if i % 2:
                assert w.streams[i].q.empty()
            else:
                assert w.next_event(i) == ([_ACK], "cancelled")
                assert w.streams[i].q.empty()
        assert w.watcher.counts()["cancel"] == 4
    finally:
        w.close()


@pytest.mark.parametrize("terminal", [
    _END, error_to_wire(ServingError("decode failed"))],
    ids=["end", "error"])
@pytest.mark.parametrize("cancel", ["consumed", "unread", "none"])
def test_the_hand_back_answers_a_cancel_exactly_once(terminal, cancel):
    """The stream's own terminal event races the client's cancel. The
    handler takes the connection out of the watcher BEFORE it writes the
    terminal line; a cancel line the watcher had consumed by then is
    answered by the handler, once, right after that line (the idle ack
    the substrate would have given), and one it had not is left in the
    socket for the substrate, as is the next request's line: the watcher
    never reads a connection that was handed back."""
    w = _Wired()
    try:
        w.streams[0].q.put(dict(terminal))  # the stream ended first
        if cancel == "consumed":
            w.clients[0].sendall(_CANCEL_LINE)
            assert _until(lambda: w.watcher.counts()["cancel"] == 1)
        lines, ended = w.next_event()
        assert ended == ("ok" if terminal is _END else "error")
        assert lines == ([terminal, _IDLE_ACK] if cancel == "consumed"
                         else [terminal])
        counts = w.watcher.counts()
        assert counts["watching"] == 0
        # what the client sends now is the substrate's to read
        sent = []
        if cancel == "unread":
            sent.append(_CANCEL_LINE)
        sent.append(b'{"method": "health"}\n')
        for line in sent:
            w.clients[0].sendall(line)
        time.sleep(0.1)
        assert w.watcher.counts()["wakeups"] == counts["wakeups"]
        assert [w.conns[0].rfile.readline() for _ in sent] == sent
        # the verdict the watcher posted is in a queue nobody reads
        assert w.cancelled == ([w.streams[0]] if cancel == "consumed"
                               else [])
        assert w.watcher.unwatch(w.conns[0]) is False  # idempotent
    finally:
        w.close()


@pytest.mark.parametrize("tail", [b'cel"}\n', b"", b'ned"}\n'],
                         ids=["cancel", "eof", "other"])
def test_a_partial_line_neither_blocks_nor_spins_the_watcher(tail):
    """Readable bytes with no newline yet (a cancel line sent in two
    fragments, a trickling client): ``readline`` would block the watcher
    and a level-triggered selector would spin it, so the socket is taken
    out and looked at again after ``stream_poll_s``. Meanwhile the
    handler sleeps, and another connection's cancel is read at once."""
    w = _Wired(n=2, pause_s=0.05)
    try:
        t0 = time.monotonic()
        w.clients[0].sendall(b'{"method": "can')
        time.sleep(0.3)
        counts = w.watcher.counts()
        # one a pause, not one a spin of the selector
        assert 1 <= counts["wakeups"] <= 2 + (time.monotonic() - t0) / 0.05
        assert (counts["cancel"], counts["eof"]) == (0, 0)
        assert counts["watching"] == 2
        assert w.streams[0].q.empty() and not w.cancelled
        w.clients[1].sendall(_CANCEL_LINE)       # not behind the partial
        assert w.next_event(1) == ([_ACK], "cancelled")
        if tail:
            w.clients[0].sendall(tail)
        else:
            w.clients[0].shutdown(socket.SHUT_WR)
        if tail.startswith(b"ned"):
            # a whole line that is no cancel: consumed and ignored, the
            # stream goes on and the connection is watched as before
            w.streams[0].q.put(_CHUNK)
            assert w.next_event(0) == ([_CHUNK], None)
            assert _until(lambda: not w.watcher._paused)
            w.clients[0].sendall(_CANCEL_LINE)
        assert w.next_event(0) == (
            ([_ACK], "cancelled") if tail else ([], "disconnect"))
        assert w.cancelled == [w.streams[1], w.streams[0]]
        counts = w.watcher.counts()
        assert counts["cancel"] + counts["eof"] == 2
        assert counts["eof"] == (0 if tail else 1)
        assert counts["handler_empty_wakeups"] == 0
    finally:
        w.close()


def _gated(sess):
    """Hold the decode worker inside its step dispatch until the returned
    event is set: the streams it serves are in flight and idle."""
    gate = threading.Event()
    step = sess.step
    sess.step = lambda: (gate.wait(60), step())[1]
    return gate


@pytest.mark.parametrize("how", ["eof", "kill"])
def test_a_client_gone_mid_stream_is_read_by_the_watcher(trained, how):
    """The client closes its socket (``eof``) or is killed (``kill``: the
    close sends a reset) while its stream is in flight and its handler
    asleep on the queue: the watcher reads it, the stream is cancelled on
    the worker and observed as ``disconnect``; slot and pages are free
    when the worker's next pass has run."""
    import struct

    src = trained["src"]
    sess = _paged(trained)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        cl.generate_full(src[0], src_len=SEQ)   # the compiles, not the race
        gate = _gated(sess)
        try:
            gen = cl.generate(src[2], src_len=SEQ)
            assert [next(gen)["event"], next(gen)["event"]] == [
                "queued", "admitted"]
            assert _until(
                lambda: fe._watcher.counts()["watching"] == 1)
            if how == "kill":
                cl._sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                    struct.pack("ii", 1, 0))
            cl.close()
            # read while the worker is still inside its step
            assert _until(lambda: fe._watcher.counts()["eof"] == 1)
            assert sess.active_slots
        finally:
            gate.set()
        assert _drained(sess)
        assert sess.free_slots == S and sess.pool_conserved
        assert sess.free_pages == sess._P - 1 - sess.cached_pages
        assert _until(lambda: fe.stats()["active_streams"] == 0)
        stats = fe.stats()
        assert stats["requests"]["generate"] == {"ok": 1, "disconnect": 1}
        assert stats["watcher"]["watching"] == 0
        assert stats["watcher"]["cancel"] == 0
        assert stats["watcher"]["handler_empty_wakeups"] == 0


def _parked_streams(fe, src, n):
    """``n`` client threads, each consuming one stream to its end (into
    ``got``, or its exception into ``errors``)."""
    got, errors = {}, {}

    def consume(i):
        cl = ServingClient(fe.address, timeout_s=60.0)
        try:
            got[i] = cl.generate_full(src[i], src_len=SEQ)
        except Exception as exc:  # noqa: BLE001 - asserted by the caller
            errors[i] = exc
        finally:
            cl.close()

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    return threads, got, errors


def test_idle_streams_wake_nobody_and_the_gauge_follows_them(trained):
    """Six streams in flight for a second with nothing to write (four
    admitted, the worker held inside their step; two behind them): no
    handler thread wakes, the watcher does not wake, its gauge reads
    six; when they have ended it reads none. (The polling handlers woke
    twenty times a second for every open stream.)"""
    src = trained["src"]
    sess = _paged(trained)
    with ServingFrontend(session=sess) as fe:
        ServingClient(fe.address).generate_full(src[0], src_len=SEQ)
        gate = _gated(sess)
        try:
            threads, got, errors = _parked_streams(fe, src, 6)
            assert _until(lambda: fe._watcher.counts()["watching"] == 6)
            time.sleep(0.3)   # the first pass's events are written

            def woken():   # (a live stream's are on the stream)
                streams = [w.stream
                           for w in list(fe._watcher._watched.values())]
                return (sum(st.wakeups for st in streams),
                        sum(st.empty_wakeups for st in streams))

            before, handlers = fe._watcher.counts(), woken()
            time.sleep(1.0)
            assert woken() == handlers and handlers[1] == 0
            after = fe.stats()["watcher"]
            assert after == before
            assert after["watching"] == 6
        finally:
            gate.set()
        for t in threads:
            t.join(timeout=60)
        assert not errors and sorted(got) == list(range(6))
        assert _until(lambda: fe._watcher.counts()["watching"] == 0)
        done = fe._watcher.counts()
        assert done["handler_wakeups"] > before["handler_wakeups"]
        assert (done["cancel"], done["eof"]) == (0, 0)
        assert done["handler_empty_wakeups"] == 0
        assert _drained(sess)


def test_close_joins_the_watcher_and_wakes_every_parked_handler(trained):
    """``close`` with a decode worker that cannot answer (held inside its
    step past the join's timeout): the handlers parked on their streams'
    queues are woken by the watcher as it stops, their connections are
    severed, no client hangs, and the watcher's thread is joined."""
    src = trained["src"]
    sess = _paged(trained)
    fe = ServingFrontend(session=sess)
    ServingClient(fe.address).generate_full(src[0], src_len=SEQ)
    gate = _gated(sess)
    try:
        threads, got, errors = _parked_streams(fe, src, 3)
        assert _until(lambda: fe._watcher.counts()["watching"] == 3)
        fe.close(drain=False, timeout=0.5)
        assert not fe._watcher._thread.is_alive()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        assert not got and sorted(errors) == [0, 1, 2]
        assert all(isinstance(exc, (ServerClosedError, StreamBrokenError,
                                    ConnectionError, OSError))
                   for exc in errors.values()), errors
        assert _until(lambda: fe.stats()["active_streams"] == 0)
        assert fe._watcher.counts()["watching"] == 0
    finally:
        gate.set()
    fe._decode._thread.join(timeout=30)
    assert _drained(sess)
    fe.close()   # idempotent, the watcher included


def test_an_attached_stream_is_watched_too(trained):
    """``attach`` shares ``generate``'s consume loop: its connection is in
    the watcher while the stream is in flight, its cancel is read there,
    answered once, and the connection serves the next request."""
    src = trained["src"]
    sess = _paged(trained)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        cl.generate_full(src[0], src_len=SEQ)
        try:
            chaos.configure("slow@site=serve.dispatch,p=1.0,secs=0.3")
            rid = fe._decode.call(lambda: sess.enqueue(src[5], SEQ))
            assert _until(lambda: rid in sess._owner.values())
            cl._send_line({"method": "attach", "id": int(rid)})
            first = cl._recv_line()
            assert first["event"] == "resumed" and not first["finished"]
            assert fe._watcher.counts()["watching"] == 1
            cl._send_line({"method": "cancel"})
            events = [cl._recv_line()]
            while events[-1].get("event") != "cancelled":
                events.append(cl._recv_line())
        finally:
            chaos.disable()
        assert events[-1] == _ACK
        assert all(ev["event"] == "tokens" for ev in events[:-1])
        assert _drained(sess)
        assert cl.generate_full(src[1], src_len=SEQ).shape == (1, SEQ)
        cl.close()
        stats = fe.stats()
        assert stats["requests"]["attach"] == {"cancelled": 1}
        assert (stats["watcher"]["cancel"], stats["watcher"]["eof"]) \
            == (1, 0)
        assert stats["watcher"]["watching"] == 0


def _tls_contexts(tmp_path):
    """A self-signed server context and the client context that trusts it."""
    import datetime
    import ipaddress
    import ssl

    x509 = pytest.importorskip("cryptography.x509")
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec

    key = ec.generate_private_key(ec.SECP256R1())
    name = x509.Name([x509.NameAttribute(
        x509.oid.NameOID.COMMON_NAME, "localhost")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(name).issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(days=1))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.SubjectAlternativeName([x509.IPAddress(
                ipaddress.ip_address("127.0.0.1"))]), critical=False)
            .sign(key, hashes.SHA256()))
    cert_pem, key_pem = str(tmp_path / "cert.pem"), str(tmp_path / "key.pem")
    with open(cert_pem, "wb") as f:
        f.write(cert.public_bytes(serialization.Encoding.PEM))
    with open(key_pem, "wb") as f:
        f.write(key.private_bytes(
            serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
            serialization.NoEncryption()))
    server = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    server.load_cert_chain(cert_pem, key_pem)
    client = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    client.load_verify_locations(cert_pem)
    return server, client


@pytest.mark.parametrize("how", ["cancel", "eof"])
def test_a_tls_stream_is_cancelled_by_its_own_handler(trained, tmp_path,
                                                      how):
    """An SSL socket cannot be peeked, and is not safe to read on one
    thread while another writes: on a TLS frontend the watcher only says
    that bytes came (the raw socket turned readable) and the stream's
    handler, woken once, reads the line on the thread that writes. The
    cancel is answered with the stream's own ``cancelled`` and frees the
    slot; the connection serves the next request. (The polling handler
    peeked, which an SSL socket refuses: an untyped error line, and the
    slot decoded on to its stream's natural end.)"""
    src = trained["src"]
    sess = _paged(trained)
    server_ctx, client_ctx = _tls_contexts(tmp_path)
    with ServingFrontend(session=sess, ssl_context=server_ctx) as fe:
        cl = ServingClient(fe.address, ssl_context=client_ctx)
        cl.generate_full(src[0], src_len=SEQ)
        gate = _gated(sess)
        try:
            gen = cl.generate(src[2], src_len=SEQ)
            assert [next(gen)["event"], next(gen)["event"]] == [
                "queued", "admitted"]
            assert _until(
                lambda: fe._watcher.counts()["watching"] == 1)
            if how == "cancel":
                gen.close()   # sends the cancel line, reads to the ack
            else:
                cl.close()
            outcome = {"cancel": "cancelled", "eof": "disconnect"}[how]
            assert _until(lambda: (fe.stats()["requests"]["generate"]
                                   == {"ok": 1, outcome: 1}))
            assert sess.active_slots   # the worker is still in its step
        finally:
            gate.set()
        assert _drained(sess)
        if how == "cancel":
            assert cl.generate_full(src[1], src_len=SEQ).shape == (1, SEQ)
            cl.close()
        counts = fe.stats()["watcher"]
        assert counts["watching"] == 0
        assert counts["handler_empty_wakeups"] == 0


def _race_cancels(cl, src, rounds, out):
    """One connected client: ``rounds`` streams, each cancelled after 0 to 5 of its
    chunks have been read (the last of them with the stream's own ``end``
    already behind them). Every cancel must bring exactly ONE
    ``cancelled`` back, the stream's own or an ack behind its ``end``, and
    the very next line on the connection must answer the next request.
    ``out``: how many went each way, or the failure."""
    request = {"method": "generate", "n": 1, "src_len": SEQ,
               "src": encode_array(src.astype("int64"))}
    by_stream = by_ack = 0
    try:
        for i in range(rounds):
            cl._send_line(request)
            chunks, ended = 0, False
            while chunks < i % 6 and not ended:
                ev = cl._recv_line()
                assert ev["ok"], ev
                chunks += ev["event"] == "tokens"
                ended = ev["event"] == "end"
            cl._send_line({"method": "cancel"})
            ev = cl._recv_line()
            while ev.get("event") != "cancelled":
                assert ev["ok"] and ev["event"] in (
                    "queued", "admitted", "tokens", "end"), ev
                ended |= ev["event"] == "end"
                ev = cl._recv_line()
            # a stream's own ``cancelled`` ends it; an ack follows an end
            assert ev == (_IDLE_ACK if ended else _ACK), (i, ev)
            by_ack += ended
            by_stream += not ended
            # in step: the next line is the next request's answer
            cl._send_line({"method": "health"})
            assert "health" in cl._recv_line(), i
        out.append((by_stream, by_ack))
    except BaseException as exc:  # noqa: BLE001 - reported by the test
        out.append(exc)
        raise
    finally:
        cl.close()


@pytest.mark.parametrize("clients,rounds", [(1, 300), (16, 25)])
def test_a_cancel_racing_the_end_is_answered_exactly_once(trained, clients,
                                                          rounds):
    """Over real sockets and a real session, a few hundred cancels that
    race their streams' ends: however each race resolves (the handler's
    terminal ``cancelled``; ``end`` and then the ack the handler owed;
    ``end`` and then the substrate's idle ack) the client reads exactly
    one ``cancelled`` and its connection stays in step. With sixteen
    clients (more threads than cores, a short switch interval) the
    watcher, sixteen handlers and the worker share the hand-back's lock."""
    src = trained["src"]
    sess = _paged(trained, sampler=None)
    interval = sys.getswitchinterval()
    with ServingFrontend(session=sess) as fe:
        ServingClient(fe.address).generate_full(src[1], src_len=SEQ)
        out = []
        # connected one by one: the substrate listens with a backlog of 5
        conns = [ServingClient(fe.address, timeout_s=60.0)
                 for _ in range(clients)]
        for cl in conns:
            cl._send_line({"method": "health"})
            assert "health" in cl._recv_line()
        threads = [threading.Thread(target=_race_cancels,
                                    args=(cl, src[1], rounds, out))
                   for cl in conns]
        try:
            if clients > 1:
                sys.setswitchinterval(1e-5)
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert len(out) == clients
        assert not [exc for exc in out if isinstance(exc, BaseException)]
        by_stream = sum(n for n, _ in out)
        by_ack = sum(n for _, n in out)
        assert _drained(sess)
        assert _until(lambda: fe.stats()["active_streams"] == 0)
        stats = fe.stats()
        assert by_stream + by_ack == clients * rounds
        assert by_stream and by_ack
        assert stats["requests"]["generate"] == {
            "ok": 1 + by_ack, "cancelled": by_stream}
        # the cancels the watcher read: every stream's own, and those of
        # the acks that the handlers owed (the substrate gave the others)
        assert by_stream <= stats["watcher"]["cancel"] <= clients * rounds
        assert stats["watcher"]["eof"] == 0
        assert stats["watcher"]["watching"] == 0
        assert stats["watcher"]["handler_empty_wakeups"] == 0


def _oracle_after_cancel(oracle, src):
    slot = oracle.admit(src[3], SEQ)
    oracle.cancel(slot)
    return oracle.generate(src[4][None, :], [SEQ])


def test_session_cancel_is_conservation_clean(trained):
    """The session-level teardown primitive itself: cancel a live fork
    group member mid-decode, conservation holds, the slot re-admits."""
    src = trained["src"]
    sess = _paged(trained)
    slots = sess.admit_group(src[0], n=2, src_len=SEQ,
                             prefix_tokens=[int(t) for t in src[0][:4]])
    assert sess.cancel(slots[0]) is True
    assert sess.cancel(slots[0]) is False  # idempotent
    assert sess.pool_conserved
    sess.step()  # the surviving member decodes on
    if slots[1] in sess.active_slots:
        assert sess.cancel(slots[1]) is True
    assert sess.pool_conserved and sess.free_slots == S
    assert sess.free_pages == sess._P - 1 - sess.cached_pages


def test_close_drain_false_fails_streams_typed_and_reclaims(trained):
    src = trained["src"]
    sess = _paged(trained)
    fe = ServingFrontend(session=sess)
    cl = ServingClient(fe.address)
    gen = cl.generate(src[5], src_len=SEQ)
    next(gen)
    got = []

    def drain():
        try:
            for _ in gen:
                pass
        except Exception as exc:  # noqa: BLE001 - asserted below
            got.append(exc)

    t = threading.Thread(target=drain)
    t.start()
    fe.close(drain=False)
    t.join(timeout=30)
    assert not t.is_alive(), "stream consumer hung across close"
    if got:  # either the typed close error or the severed connection
        assert isinstance(got[0], (ServerClosedError, StreamBrokenError,
                                   ConnectionError, OSError)), got[0]
    assert _drained(sess)
    cl.close()


def test_bad_request_is_typed_and_worker_survives(trained):
    """A request the session type refuses (forced prefix on a DENSE
    session) surfaces as a typed wire error from the admission path —
    and must NOT kill the decode worker: the next request still
    serves."""
    src = trained["src"]
    sess = SlotDecodeSession(
        trained["exe"], num_slots=S, max_length=SEQ, d_model=D,
        paged=False, scope=trained["scope"].new_scope(), **CFG)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        with pytest.raises(ServingError):
            cl.generate_full(src[0], src_len=SEQ,
                             prefix_tokens=[3, 4])
        # the worker lived through it: a well-formed request serves
        out = cl.generate_full(src[0], src_len=SEQ)
        assert out.shape == (1, SEQ)
        cl.close()


# ---------------------------------------------------------------------------
# ops endpoints
# ---------------------------------------------------------------------------

def test_metrics_health_stats_endpoints(demo_predictor, trained):
    server = BatchingServer(demo_predictor, max_batch=8, workers=1)
    sess = _paged(trained)
    with server, ServingFrontend(server=server, session=sess) as fe:
        cl = ServingClient(fe.address)
        cl.predict({"x": np.zeros((2, 12), dtype="float32")})
        cl.generate_full(trained["src"][6], src_len=SEQ)
        text = cl.metrics()
        assert "paddle_tpu_frontend_request_seconds" in text
        assert "paddle_tpu_frontend_active_connections" in text
        assert "paddle_tpu_frontend_bytes_sent_total" in text
        assert "paddle_tpu_frontend_ttft_seconds" in text
        # the scrape over the wire carries the compile counter too
        assert "paddle_tpu_fresh_compiles_total" in text
        health = cl.health()
        assert health == {"server": "healthy", "decode": "healthy"}
        stats = cl.stats()
        assert stats["requests"]["predict"]["ok"] >= 1
        assert stats["requests"]["generate"]["ok"] >= 1
        assert stats["active_connections"] >= 1
        assert stats["bytes_sent"] > 0 and stats["bytes_received"] > 0
        assert cl.take_result(10 ** 9) is None
        cl.close()


# ---------------------------------------------------------------------------
# chaos: net.accept / net.send + classified retry — never a hang
# ---------------------------------------------------------------------------

def test_net_accept_fault_is_survived_by_reconnect(demo_predictor):
    server = BatchingServer(demo_predictor, max_batch=8, workers=1)
    with server, ServingFrontend(server=server) as fe:
        flags.set_flag("chaos_spec", "seed=3;io@site=net.accept,n=1")
        chaos.configure()
        cl = ServingClient(fe.address)
        out = cl.predict({"x": np.zeros((2, 12), dtype="float32")})
        assert len(out) == 1
        assert chaos.fires("net.accept") == 1, \
            "the accept fault never fired: the test is vacuous"
        cl.close()


def test_net_send_fault_unary_is_retried(demo_predictor):
    server = BatchingServer(demo_predictor, max_batch=8, workers=1)
    with server, ServingFrontend(server=server) as fe:
        flags.set_flag("chaos_spec", "seed=3;io@site=net.send,n=1")
        chaos.configure()
        cl = ServingClient(fe.address)
        # the response write fails -> severed connection -> the
        # client's reconnect-retry-once re-sends and succeeds
        out = cl.predict({"x": np.zeros((2, 12), dtype="float32")})
        assert len(out) == 1
        assert chaos.fires("net.send") == 1
        cl.close()


def test_net_send_fault_mid_stream_is_typed_never_a_hang(trained):
    src = trained["src"]
    sess = _paged(trained)
    with ServingFrontend(session=sess) as fe:
        # skip the queued/admitted/first-token sends, then sever: the
        # client has consumed tokens, so the break is NOT silently
        # retried — it surfaces as the typed StreamBrokenError
        flags.set_flag("chaos_spec",
                       "seed=3;io@site=net.send,skip=3,n=1")
        chaos.configure()
        cl = ServingClient(fe.address)
        t0 = time.monotonic()
        with pytest.raises(StreamBrokenError):
            cl.generate_full(src[7], src_len=SEQ)
        assert time.monotonic() - t0 < 30.0, "broken stream hung"
        assert chaos.fires("net.send") == 1
        chaos.disable()
        # the severed write tore the stream down server-side too
        assert _drained(sess)
        assert sess.pool_conserved
        cl.close()


def test_client_reads_are_watchdog_armed(demo_predictor, monkeypatch):
    from paddle_tpu.serving import client as client_mod

    armed = []
    monkeypatch.setattr(client_mod._watchdog, "ENABLED", True)
    real_arm = client_mod._watchdog.arm

    def spy_arm(tag="work", scale=1):
        armed.append(tag)
        return real_arm(tag, scale)

    monkeypatch.setattr(client_mod._watchdog, "arm", spy_arm)
    server = BatchingServer(demo_predictor, max_batch=8, workers=1)
    with server, ServingFrontend(server=server) as fe:
        cl = ServingClient(fe.address)
        cl.predict({"x": np.zeros((2, 12), dtype="float32")})
        cl.close()
    assert "net.recv" in armed


def test_client_survives_frontend_restart(demo_predictor):
    server = BatchingServer(demo_predictor, max_batch=8, workers=1)
    req = {"x": np.zeros((2, 12), dtype="float32")}
    with server:
        fe = ServingFrontend(server=server)
        host, port = fe.address
        cl = ServingClient(fe.address)
        want = cl.predict(req)
        fe.close()
        # restart on the SAME port: the established connection is
        # severed; the client's reconnect-retry-once rides through
        fe2 = ServingFrontend(server=server, host=host, port=port)
        got = cl.predict(req)
        assert np.array_equal(got[0], want[0])
        cl.close()
        fe2.close()


# ---------------------------------------------------------------------------
# SIGTERM composition with DecodeSnapshotManager (subprocess)
# ---------------------------------------------------------------------------

_CHILD = r"""
import os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import paddle_tpu as fluid
from paddle_tpu.models import transformer
from paddle_tpu.serving.frontend import ServingFrontend
from paddle_tpu.serving.generation import Sampler, SlotDecodeSession
from paddle_tpu.serving.snapshot import DecodeSnapshotManager

snap_dir = sys.argv[1]
VOCAB, SEQ, D, S = 24, 8, 32, 4
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=1,
           n_head=2, d_inner=64)
main, startup = fluid.Program(), fluid.Program()
main.random_seed = 41; startup.random_seed = 41
with fluid.program_guard(main, startup):
    transformer.build(dropout=0.0, label_smooth_eps=0.0,
                      max_length=SEQ, d_model=D, **CFG)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
sess = SlotDecodeSession(exe, num_slots=S, max_length=SEQ, d_model=D,
                         paged=True, page_size=4, steps=2,
                         sampler=Sampler(seed=3), **CFG)
# order matters: the manager's handlers first, the frontend's on top —
# a SIGTERM stops the transport, then chains into the snapshot path
mgr = DecodeSnapshotManager(sess, snap_dir,
                            install_signal_handlers=True)
fe = ServingFrontend(session=sess, install_signal_handlers=True)
print("PORT %d" % fe.port, flush=True)
while True:
    time.sleep(0.1)
"""


@pytest.mark.slow
def test_sigterm_frontend_banks_backlog_and_dies_by_signal(tmp_path):
    snap_dir = str(tmp_path / "snap")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLAGS_chaos_spec", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD, snap_dir],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
        cwd=os.path.join(os.path.dirname(__file__), os.pardir))
    streams_alive = []
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("PORT "), (line, proc.stderr.read())
        port = int(line.split()[1])
        rng = np.random.RandomState(7)
        src = rng.randint(3, VOCAB, (8, SEQ)).astype("int64")

        def streamer(i):
            cl = ServingClient(("127.0.0.1", port), timeout_s=60.0)
            try:
                for _ in cl.generate(src[i], src_len=SEQ):
                    pass
            except Exception:  # noqa: BLE001 - severed by the SIGTERM
                pass
            finally:
                cl.close()

        # a backlog bigger than the pool: some live, some queued
        threads = [threading.Thread(target=streamer, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        streams_alive = threads
        time.sleep(1.0)  # let admissions land
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        proc.kill()
        for t in streams_alive:
            t.join(timeout=30)
    assert proc.returncode == -signal.SIGTERM, (proc.returncode, err)
    from paddle_tpu.resilience.checkpoint import (
        complete_serials,
        read_manifest,
    )

    serials = complete_serials(snap_dir)
    assert serials, "no final snapshot banked on SIGTERM: %s" % err
    manifest = read_manifest(
        os.path.join(snap_dir, "checkpoint_%d" % serials[-1]))
    meta = manifest["extra"]["decode_snapshot"]
    assert meta["live"] or meta["pending"], (
        "SIGTERM'd frontend banked no backlog (live=%r pending=%r)"
        % (meta["live"], meta["pending"]))
