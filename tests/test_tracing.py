"""Request-scoped tracing (observability/tracing.py + the serving
hooks):

* trace lifecycle: root span covers the handling window, leaked spans
  force-close (flagged), derived stats (TTFT, phase split, inter-token
  distribution, span coverage) come out of the span timeline;
* histogram exemplars land in the narrowest bucket, ride the JSON
  snapshot, and resolve against the completed-trace ring;
* Perfetto export is structurally valid Chrome trace JSON;
* CONTINUITY across preemption: a session snapshotted mid-flight
  restores with its ``rid -> trace_id`` bindings intact, re-banks its
  backlogged streams under the ORIGINAL ids (session-origin
  continuation records), and ``take_result`` still names the trace at
  claim time — the frontend's post-restore claim path;
* cancel / drop paths close every span: the ring sweep finds no open
  or force-closed spans and the in-flight table drains to empty;
* blackbox snapshots list in-flight trace ids.

Tracing must also be FREE when off: the session allocates nothing, and
over a real socket the same streams come back with no trace field in any
envelope and nothing in the rings.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.executor import global_scope
from paddle_tpu.observability import blackbox, tracing
from paddle_tpu.observability.metrics_registry import (
    DECODE_BUCKETS,
    MetricsRegistry,
)
from paddle_tpu.serving.generation import Sampler, SlotDecodeSession
from paddle_tpu.serving.snapshot import DecodeSnapshotManager

VOCAB, SEQ, D, S = 24, 8, 32, 4
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=2,
           n_head=2, d_inner=64)


@pytest.fixture(scope="module")
def trained():
    """One tiny transformer shared by the module (the serving
    resilience suite's pattern)."""
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
    src_len = np.asarray([SEQ, 3, SEQ - 1, 5, SEQ, 4, SEQ - 2, SEQ],
                         "int64")
    return {"exe": exe, "scope": scope, "src": src, "src_len": src_len}


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


@pytest.fixture(autouse=True)
def _tracing_reset():
    tracing.reset()
    tracing.enable(True)
    yield
    tracing.enable(False)
    tracing.reset()


def _sweep_ring(recs):
    """The span-closure sweep: every span in every completed record is
    closed, and none was force-closed at finish (a force-close means a
    code path finished the trace with a span still open)."""
    for rec in recs:
        for sp in rec["spans"]:
            assert sp["t1"] is not None, (
                "open span %r in completed trace %s"
                % (sp["name"], rec["trace_id"]))
            assert not sp["meta"].get("force_closed"), (
                "force-closed span %r leaked to finish in trace %s "
                "(outcome=%s)" % (sp["name"], rec["trace_id"],
                                  rec["outcome"]))


# -- unit: lifecycle, stats, ring, exemplars, perfetto -----------------------

def test_trace_lifecycle_and_derived_stats():
    tr = tracing.start(endpoint="generate", t_client_send=None)
    assert tr.id in tracing.inflight_ids()
    tr.span("queue", tr.t0, tr.t0 + 0.001)
    sp = tr.begin("prefill", prefix_hit_pages=2)
    tr.end(sp)
    for _ in range(3):
        d = tr.begin("decode.step", tokens=2, cow_copies=1,
                     speculative=True)
        tr.end(d)
        tr.bump("tokens", 2)
        tr.bump("tokens_from_spec", 1)
        tr.bump("cow_copies", 1)
    tr.mark("first_token")
    tr.mark("first_token")  # idempotent: first occurrence wins
    # the queue span above ends 1 ms after the trace began: on a fast
    # machine the body takes less, and the root span would not cover it
    time.sleep(max(0.0, tr.t0 + 0.002 - time.time()))
    rec = tracing.finish(tr, outcome="ok")
    assert tr.id not in tracing.inflight_ids()
    st = rec["stats"]
    assert st["tokens"] == 6 and st["tokens_from_spec"] == 3
    assert st["spec_fraction"] == 0.5 and st["cow_copies"] == 3
    assert st["queue_s"] == pytest.approx(0.001, abs=5e-4)
    assert st["ttft_s"] is not None and st["wall_s"] > 0
    # the root "request" span spans the whole window -> full coverage
    assert st["span_coverage"] == 1.0
    assert tracing.get(tr.id) is rec and rec["outcome"] == "ok"
    _sweep_ring([rec])


def test_finish_force_closes_leaked_spans_and_flags_them():
    tr = tracing.start(endpoint="generate")
    tr.begin("decode.step")  # never ended
    rec = tracing.finish(tr, outcome="error")
    leaked = [sp for sp in rec["spans"]
              if sp["meta"].get("force_closed")]
    assert len(leaked) == 1 and leaked[0]["name"] == "decode.step"
    # the root span closes at finish by design, never flagged
    assert not any(sp["meta"].get("force_closed")
                   for sp in rec["spans"] if sp["name"] == "request")


def test_mint_ids_unique_and_ring_is_bounded():
    ids = {tracing.mint_id() for _ in range(64)}
    assert len(ids) == 64 and all(len(i) == 16 for i in ids)
    for _ in range(tracing.RING + 5):
        tracing.finish(tracing.start(endpoint="generate"))
    assert len(tracing.completed()) == tracing.RING


def test_histogram_exemplar_lands_in_narrowest_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("t_seconds", "t", buckets=DECODE_BUCKETS)
    h.observe(0.0008, exemplar="aaaa")   # -> the 0.001 bucket (idx 3)
    h.observe(0.0009, exemplar="bbbb")   # same bucket: last writer wins
    h.observe(99.0, exemplar="cccc")     # -> +Inf overflow bucket
    ex = h.exemplars()
    assert ex[3]["id"] == "bbbb" and ex[3]["value"] == 0.0009
    assert ex[len(DECODE_BUCKETS)]["id"] == "cccc"
    snap = h.snapshot()
    assert snap["exemplars"][3]["id"] == "bbbb"
    # an untraced observation never allocates exemplar state
    h2 = reg.histogram("p_seconds", "p", buckets=DECODE_BUCKETS)
    h2.observe(0.001)
    assert h2.exemplars() == {} and "exemplars" not in h2.snapshot()


def test_exemplar_resolves_against_completed_ring():
    tr = tracing.start(endpoint="generate")
    rec = tracing.finish(tr)
    assert tracing.get(tr.id) is rec
    assert tracing.get("0000000000000000") is None


def test_perfetto_events_are_valid_chrome_trace():
    tr = tracing.start(endpoint="generate")
    sp = tr.begin("decode.step", tokens=2)
    tr.end(sp)
    rec = tracing.finish(tr)
    events = tracing.perfetto_events(rec, row=3, pid=9)
    kinds = {e["ph"] for e in events}
    assert kinds == {"M", "X"}
    slices = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in slices} == {"request", "decode.step"}
    for e in slices:
        assert e["pid"] == 9 and e["tid"] == 3
        assert e["dur"] >= 0 and e["ts"] > 0
        assert e["args"]["trace_id"] == rec["trace_id"]


def test_blackbox_snapshot_lists_inflight_traces():
    tr = tracing.start(endpoint="generate")
    entries = blackbox.snapshot(reason="test")["inflight_traces"]
    mine = [e for e in entries if e["trace_id"] == tr.id]
    assert mine and mine[0]["endpoint"] == "generate"
    assert mine[0]["spans_open"] == 1  # the root span
    tracing.finish(tr)
    assert not [e for e in
                blackbox.snapshot(reason="test")["inflight_traces"]
                if e["trace_id"] == tr.id]


# -- session integration: continuity, cancel, page accounting ----------------

def test_traced_backlog_rides_snapshot_under_original_ids(trained,
                                                          tmp_path):
    """THE continuity property: a session snapshotted with traced
    requests mid-flight restores with the rid -> trace-id bindings
    intact, re-banks the backlog under the ORIGINAL ids, and
    take_result still names each trace at claim time."""
    src, src_len = trained["src"], trained["src_len"]
    victim = _paged(trained)
    tids = {}
    for i in range(1, 6):
        tid = tracing.mint_id()
        rid = victim.enqueue(src[i], int(src_len[i]), trace_id=tid)
        tids[rid] = tid
    for _ in range(2):
        victim.pump()
    assert victim._pending, "snapshot point too late to carry backlog"
    assert victim._trace_ids, "bindings already retired"
    mgr = DecodeSnapshotManager(victim, str(tmp_path / "snap"))
    mgr.save()
    mgr.close(save=False)

    # simulate the process boundary: the restored twin has no in-flight
    # traces — continuation must START session-origin traces from the
    # restored bindings, not find frontend ones
    tracing.reset()
    restored = _paged(trained)
    mgr2 = DecodeSnapshotManager(restored, str(tmp_path / "snap"))
    assert mgr2.restore() is not None
    # the bindings survived the dialect round trip verbatim
    assert restored._trace_ids == {
        rid: tid for rid, tid in tids.items()
        if rid in victim._trace_ids}
    for _ in range(40):
        restored.pump()
        if not restored.pending_requests and not restored.active_slots:
            break
    banked = {rec["trace_id"]: rec for rec in tracing.completed()}
    for rid in list(tids):
        tokens = restored.take_result(rid)
        if tokens is None:
            continue  # claimed by the pre-snapshot victim pumps
        tid = tids[rid]
        rec = banked.get(tid)
        assert rec is not None, (
            "restored request %d re-banked under a NEW id, not its "
            "original trace %s" % (rid, tid))
        assert rec["origin"] == "session" and rec["outcome"] == "banked"
        assert any(sp["name"] == "decode.step" for sp in rec["spans"])
    # claims retired every binding
    assert not restored._trace_ids
    assert not tracing.inflight_ids()
    _sweep_ring(tracing.completed())
    mgr2.close(save=False)


def test_cancel_and_drop_close_every_span(trained):
    """Cancel (live slot) and drop (queued request) both finish their
    traces with no open spans — swept across the whole ring — and the
    in-flight table drains to empty."""
    src, src_len = trained["src"], trained["src_len"]
    sess = _paged(trained)
    rids = {}
    for i in range(6):
        tid = tracing.mint_id()
        rid = sess.enqueue(src[i % len(src)], int(src_len[i]),
                           trace_id=tid)
        rids[rid] = tid
    admitted = sess.admit_pending()
    assert admitted and sess._slot_traces
    sess.step()  # one dispatch so cancelled traces carry decode spans
    for slot in list(admitted):
        sess.cancel(slot)
    for rid in list(sess._trace_ids):
        sess.drop_pending(rid)
    assert not sess._slot_traces and not sess._trace_ids
    assert not tracing.inflight_ids(), (
        "cancel/drop leaked open traces: %r" % tracing.inflight_ids())
    recs = tracing.completed()
    # queued-never-admitted requests have no trace OBJECT yet (the
    # session only continues traces at admission) — dropping them just
    # retires the binding; admitted ones must finish as cancelled
    assert {r["outcome"] for r in recs} <= {"cancelled", "banked"}
    assert any(r["outcome"] == "cancelled" for r in recs)
    _sweep_ring(recs)
    assert sess.pool_conserved


def test_traced_decode_accumulates_pages_and_tokens(trained):
    """A traced request driven to completion accumulates tokens and
    integrates page-seconds; its session-origin record derives a full
    stats block."""
    src, src_len = trained["src"], trained["src_len"]
    sess = _paged(trained)
    tid = tracing.mint_id()
    rid = sess.enqueue(src[0], int(src_len[0]), trace_id=tid)
    for _ in range(40):
        sess.pump()
        if sess.take_result(rid) is not None:
            break
    rec = tracing.get(tid)
    assert rec is not None and rec["outcome"] == "banked"
    st = rec["stats"]
    assert st["tokens"] > 0
    assert st["page_seconds"] > 0
    assert st["queue_s"] >= 0 and st["prefill_s"] > 0
    assert st["decode_s"] > 0
    names = {sp["name"] for sp in rec["spans"]}
    assert {"request", "queue", "prefill", "decode.step"} <= names
    _sweep_ring([rec])


def _count_readings(monkeypatch):
    """A list that grows by one with every reading of a thread's CPU
    clock (``time.thread_time``: the round's spans, the executor's
    record and the frontend's handlers read it, with tracing on)."""
    readings = []
    thread_time = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: readings.append(1) or thread_time())
    return readings


def test_tracing_off_session_allocates_nothing(trained, monkeypatch):
    """With tracing off, the session's per-request maps stay empty —
    the zero-allocation half of the overhead contract at the session
    layer (the wire half is the next test's control leg)."""
    from paddle_tpu.observability import step_profiler

    tracing.enable(False)
    readings = _count_readings(monkeypatch)
    src, src_len = trained["src"], trained["src_len"]
    sess = _paged(trained)
    step_profiler.reset()
    rid = sess.enqueue(src[0], int(src_len[0]))
    for _ in range(40):
        sess.pump()
        if sess.take_result(rid) is not None:
            break
    assert sess._trace_ids == {} and sess._slot_traces == {}
    assert sess._trace_cow == {}
    assert tracing.completed() == [] and not tracing.inflight_ids()
    # nor does the executor's record keep the thread's CPU
    recs = step_profiler.dispatch_records()
    assert recs and readings == []
    assert all(r["cpu"] is None for r in recs)


def test_the_wire_carries_a_trace_only_with_tracing_on(trained):
    """Over a real socket. Off (the control): no trace id is minted and
    the rings stay empty. On: the SAME tokens, no fresh compile, and one
    trace a request that resolves over the wire, whose spans cover the
    wall the client saw (both read 0.97-0.98 on an idle host; the floor
    is 0.8 because the suite's workers share the cores and eight tokens
    are ~100 ms), and that the TTFT histogram's exemplar names."""
    from paddle_tpu.core import exec_cache
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.frontend import ServingFrontend, _fe_ttft

    src = trained["src"]
    rows = {}
    for on in (False, True):
        tracing.reset()
        tracing.enable(on)
        with ServingFrontend(session=_paged(trained)) as fe:
            cl = ServingClient(fe.address)
            compiled = exec_cache.stats()["fresh_compiles"]
            t0 = time.time()
            rows[on] = cl.generate_full(src[0], src_len=SEQ).tolist()
            wall = time.time() - t0
            if not on:
                assert cl.last_trace_id is None
                assert not tracing.completed() and not tracing.inflight_ids()
            else:
                assert exec_cache.stats()["fresh_compiles"] == compiled
                rec = cl.trace(cl.last_trace_id)
                assert rec and rec["trace_id"] == cl.last_trace_id
                union = tracing._union_seconds(rec["spans"], rec["t1"])
                assert union / wall >= 0.8, (union, wall, rec["stats"])
                assert rec["stats"]["span_coverage"] >= 0.8, rec["stats"]
                exemplar = next(iter(_fe_ttft.exemplars().values()))["id"]
                assert cl.trace(exemplar)["trace_id"] == exemplar
                assert not tracing.inflight_ids()
            cl.close()
    assert rows[True] == rows[False]


# -- the decode worker's rounds ----------------------------------------------

def _round_spans(rounds):
    return [(r, i, sp) for r in rounds for i, sp in enumerate(r["spans"])]


@pytest.fixture(scope="module")
def served(trained):
    """One traced scenario through a frontend over a tiny greedy session
    (greedy never samples eos here: every stream runs to max length, four
    dispatches): three wire streams, a fork group of two, and one stream
    cancelled while its slot is live. Returns copies: the autouse fixture
    resets the rings around every test."""
    import time

    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.frontend import ServingFrontend, _Stream

    src = trained["src"]
    tracing.reset()
    tracing.enable(True)
    sess = _paged(trained, sampler=None, prefix_cache_pages=0)
    read_tokens = read_chunks = 0
    try:
        with ServingFrontend(session=sess) as fe:
            cl = ServingClient(fe.address)
            for i in range(3):
                for ev in cl.generate(src[i], src_len=SEQ):
                    if ev["event"] == "tokens":
                        read_tokens += len(ev["tokens"])
                        read_chunks += 1
            for ev in cl.generate(src[3], src_len=SEQ, n=2):
                if ev["event"] == "tokens":
                    read_tokens += len(ev["tokens"])
                    read_chunks += 1
            cl.close()
            # the cancelled stream, handed to the worker directly so that
            # the cancel lands while the slot is live: the worker is held
            # at the top of a pass (its condition) while we look and cancel
            worker = fe._decode
            tr = tracing.start(endpoint="generate")
            stream = _Stream({"src": src[4], "src_len": SEQ, "n": 1,
                              "prefix": None, "beam": False,
                              "len_penalty": None, "trace_id": tr.id})
            worker.submit(stream)
            while stream.q.get(timeout=60)["event"] != "admitted":
                pass
            with worker._cond:
                was_live = bool(stream.live)
                worker.cancel(stream)
            deadline = time.monotonic() + 60
            # the slot is free as soon as the cancel ran; its round's record
            # (with the ``cancel`` span) is written when the worker's pass
            # ENDS, a moment later on a loaded machine
            while time.monotonic() < deadline and not (
                    sess.free_slots == S and sess.pool_conserved
                    and any(sp["name"] == "cancel"
                            for r in tracing.rounds() for sp in r["spans"])):
                time.sleep(0.01)
            cancelled = tracing.finish(tr, outcome="cancelled")
        out = {"rounds": tracing.rounds(), "traces": tracing.completed(),
               "read_tokens": read_tokens, "read_chunks": read_chunks,
               "handlers_open": dict(fe._handlers.open),
               "handlers_total": fe._handlers.totals(),
               "watcher": fe._watcher.counts(),
               "was_live": was_live,
               "cancelled": cancelled, "drained": sess.free_slots == S}
    finally:
        tracing.enable(False)
        tracing.reset()
    return out


def test_every_child_span_lies_inside_its_round_and_names_it(served):
    rounds = served["rounds"]
    assert rounds and len({r["id"] for r in rounds}) == len(rounds)
    names = set()
    for r, i, sp in _round_spans(rounds):
        names.add(sp["name"])
        assert sp["t1"] is not None and sp["t1"] >= sp["t0"]
        # every span holds the seconds the worker's thread ran in it
        assert 0.0 <= sp["cpu"] <= sp["t1"] - sp["t0"]
        if i == 0:
            assert sp["name"] == "round" and sp["parent"] is None
            continue
        parent = r["spans"][sp["parent"]]
        assert sp["parent"] < i
        assert parent["t0"] <= sp["t0"] and sp["t1"] <= parent["t1"]
        if sp["name"].endswith(".dispatch"):
            # an executor call, named after the span it ran under
            assert sp["name"] == parent["name"] + ".dispatch"
    assert {"round", "wait", "enqueue", "admit", "admit.dispatch", "step",
            "step.dispatch", "handoff", "cancel",
            "cancel.dispatch"} <= names


def test_child_spans_cover_the_rounds_wall(served):
    """Per round the children account for 95% of the wall: in the median
    round and over all rounds together (one round in which the scheduler
    took the thread between two spans must not fail the suite). A round
    that only queued a request is all bookkeeping: the rounds that
    dispatched are the ones measured."""
    dispatched = [r for r in served["rounds"]
                  if any(sp["name"] == "step" for sp in r["spans"])]
    assert len(dispatched) >= 8
    walls = [r["spans"][0]["t1"] - r["spans"][0]["t0"] for r in dispatched]
    covered = [sum(sp["t1"] - sp["t0"] for sp in r["spans"]
                   if sp["parent"] == 0) for r in dispatched]
    shares = sorted(c / w for c, w in zip(covered, walls))
    assert shares[len(shares) // 2] >= 0.95, shares
    assert sum(covered) >= 0.95 * sum(walls), shares


def test_round_counts_equal_what_the_session_did(served):
    rounds = served["rounds"]
    assert served["drained"] and served["was_live"]

    def total(key):
        return sum(r["spans"][0].get(key, 0) for r in rounds)

    def spans(name):
        return sum(sp["name"] == name
                   for _r, _i, sp in _round_spans(rounds))

    # three solo streams, a group of two (ONE admission of two members),
    # the cancelled stream: an admission is counted by its span; a
    # cancel span is a BATCH (here of one), the round's ``cancel_rows``
    # counts the slots and ``cancel_dispatches`` the table dispatches (a
    # lone cancel: rung 1, no padding)
    assert spans("admit") == 5 and spans("cancel") == 1
    assert total("cancel_rows") == total("cancel_dispatches") == 1
    assert spans("cancel.dispatch") == 1 and total("cancel_pad_rows") == 0
    # each of them one encoder dispatch of one source (the group's two
    # members share theirs), none padded: rung 1
    assert total("admit_dispatches") == total("admit_rows") == 5
    assert spans("admit.dispatch") == 5 + 1   # + the group's join
    assert total("admit_pad_rows") == 0
    # tokens handed to the streams: all the clients read, plus at most
    # what the cancelled stream was handed before its cancel landed
    assert served["read_tokens"] == 5 * (SEQ - 1)
    assert served["read_tokens"] <= total("tokens") \
        <= served["read_tokens"] + (SEQ - 1)
    # events put to the streams' queues: the chunks the clients read and
    # each of their four streams' ``admitted`` and ``end`` at the least;
    # none of them from inside a decode dispatch, where they are put
    # while requests wait for a slot (here no two streams were open)
    assert total("handoff_events") >= served["read_chunks"] + 4 * 2
    assert total("handoff_deferred") == 0
    for r in rounds:
        root = r["spans"][0]
        if "live" in root:
            assert 1 <= root["live"] <= S and root["backlog"] >= 0
            assert any(sp["name"] == "step" for sp in r["spans"])
            # the dispatch's cross-attention calls (2 layers x 2 token
            # steps), each a grid of S slots x 1 block of the 8-position
            # source; a copy where the group changes from slot to slot
            assert root["cross_blocks_grid"] == 2 * 2 * S
            assert 2 * 2 <= root["cross_blocks_read"] <= 2 * 2 * S
    # nothing but the documented keys: every other count had no reader
    for _r, i, sp in _round_spans(rounds):
        extra = set(sp) - {"name", "t0", "t1", "cpu", "parent"}
        assert extra <= ({"live", "backlog", "tokens", "cross_blocks_read",
                          "cross_blocks_grid", "admit_dispatches",
                          "admit_rows", "admit_pad_rows",
                          "cancel_dispatches", "cancel_rows",
                          "cancel_pad_rows", "handler_cpu",
                          "handler_chunks", "handoff_events",
                          "handoff_deferred"} | set(_WATCHER_KEYS)
                         if i == 0 else set()), sp


def test_round_roots_hold_the_handler_threads_account(served):
    """Each streamed request leaves its handler thread's CPU seconds so
    far and its chunks under its connection's key; every round's root
    holds the sums as they stood at its end: monotone, and in the end the
    chunks the clients read (the scenario's four requests went over ONE
    connection, closed by then: its key is folded into the totals)."""
    roots = [r["spans"][0] for r in served["rounds"]]
    assert all("handler_cpu" in root and "handler_chunks" in root
               for root in roots)
    cpu = [root["handler_cpu"] for root in roots]
    chunks = [root["handler_chunks"] for root in roots]
    assert cpu == sorted(cpu) and chunks == sorted(chunks)
    assert cpu[0] >= 0.0 and cpu[-1] > 0.0
    assert served["handlers_open"] == {}
    total_cpu, total_chunks = served["handlers_total"]
    assert total_chunks == served["read_chunks"] > 0
    assert chunks[-1] <= total_chunks and cpu[-1] <= total_cpu


_WATCHER_KEYS = ("handler_wakeups", "handler_empty_wakeups",
                 "watcher_watching", "watcher_wakeups", "watcher_cancel",
                 "watcher_eof", "watcher_cpu")


def test_round_roots_hold_the_connection_watchers_counts(served):
    """Beside the handlers' account every round's root holds the
    connection watcher's counts as they stood at its end: the handlers'
    wake-ups (each brought a message: none came back empty, and a handler
    that polled would have counted more wake-ups than events), the
    connections watched (the scenario's one, while a stream of its was in
    flight), the watcher's own wake-ups, verdicts and CPU. The scenario's
    clients read their streams to the end and its cancelled stream was
    handed to the worker directly: the watcher had nothing to read."""
    roots = [r["spans"][0] for r in served["rounds"]]
    assert all(key in root for root in roots for key in _WATCHER_KEYS)
    for key in _WATCHER_KEYS:
        if key != "watcher_watching":
            seen = [root[key] for root in roots]
            assert seen == sorted(seen) and seen[0] >= 0, key
    assert {root["watcher_watching"] for root in roots} <= {0, 1}
    assert any(root["watcher_watching"] == 1 for root in roots)
    assert all(root["handler_empty_wakeups"] == 0 for root in roots)
    assert all(root["watcher_cancel"] == root["watcher_eof"] == 0
               for root in roots)
    final = served["watcher"]
    assert final["watching"] == 0
    assert roots[-1]["handler_wakeups"] <= final["handler_wakeups"]
    # a wake-up an event written: the four streams' chunks and their
    # queued / admitted / end lines, and nothing else
    assert final["handler_wakeups"] == served["read_chunks"] + 3 * 3 + 2
    assert (final["cancel"], final["eof"],
            final["handler_empty_wakeups"]) == (0, 0, 0)


def test_two_connections_leave_two_keys_that_fold_as_they_close(trained):
    """Two streamed requests over two connections held open: two handler
    threads, two keys, each its own thread's CPU and the chunks its
    client read; the rounds of a third request hold the sums of both.
    A connection that closes folds its key into the totals, which only
    grow: a long-lived server keeps a key an OPEN connection."""
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.frontend import ServingFrontend

    def chunks_read(cl, i):
        return sum(ev["event"] == "tokens" for ev in
                   cl.generate(trained["src"][i], src_len=SEQ))

    def wait_for(cond):
        deadline = time.monotonic() + 60
        while not cond() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert cond()

    sess = _paged(trained, sampler=None, prefix_cache_pages=0)
    with ServingFrontend(session=sess) as fe:
        clients = [ServingClient(fe.address) for _ in range(3)]
        read = [chunks_read(clients[0], 0), chunks_read(clients[1], 1)]
        # a handler writes its account as its request ends, which its
        # client does not wait for
        wait_for(lambda: len(fe._handlers.open) == 2)
        acct = dict(fe._handlers.open)
        chunks_read(clients[2], 2)
        wait_for(lambda: len(fe._handlers.open) == 3)
        before = fe._handlers.totals()
        for cl in clients:
            cl.close()
        wait_for(lambda: not fe._handlers.open)
        after = fe._handlers.totals()
    assert len(acct) == 2 and all(r > 0 for r in read)
    assert sorted(c for _cpu, c in acct.values()) == sorted(read)
    assert all(cpu > 0.0 for cpu, _c in acct.values())
    both = [r["spans"][0] for r in tracing.rounds()
            if r["spans"][0]["handler_chunks"] == sum(read)]
    assert both
    assert both[0]["handler_cpu"] == pytest.approx(
        sum(cpu for cpu, _c in acct.values()))
    # the fold loses nothing: the same chunks, and no less CPU (a closing
    # handler's last reading is later than its last request's)
    assert after[1] == before[1] > sum(read)
    assert after[0] >= before[0]


def test_spans_split_a_blocked_threads_wall():
    """A round on a thread that sleeps (gives the interpreter lock and
    the CPU away) beside threads that spin for the lock: every span's
    CPU is under its wall, and what is not CPU -- the sleeps and the
    waits for the lock -- is most of the wall."""
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    spinners = [threading.Thread(target=spin, daemon=True)
                for _ in range(4)]
    for t in spinners:
        t.start()
    try:
        rd = tracing.round_begin()
        for name in ("cancel", "admit", "handoff"):
            with tracing.span(name):
                time.sleep(0.01)
                with tracing.span(".dispatch"):
                    for _ in range(3):
                        time.sleep(0.01)
        tracing.round_end(rd)
    finally:
        stop.set()
        for t in spinners:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in spinners)
    (r,) = tracing.rounds()
    assert [sp["name"] for sp in r["spans"]] == [
        "round", "cancel", "cancel.dispatch", "admit", "admit.dispatch",
        "handoff", "handoff.dispatch"]
    for sp in r["spans"]:
        wall = sp["t1"] - sp["t0"]
        assert 0.0 <= sp["cpu"] <= wall
        assert wall - sp["cpu"] > 0.5 * wall, sp


def test_a_round_that_cancels_several_holds_one_cancel_span(trained):
    """Three live slots handed to ``cancel_many`` in one worker pass:
    ONE ``cancel`` span with ONE ``cancel.dispatch`` child (the batch's
    table dispatch), ``cancel_rows`` the slots, the rung's other rows
    counted as padding; a lone cancel after it is a span of its own
    through rung 1."""
    sess = _paged(trained, sampler=None, prefix_cache_pages=0,
                  num_groups=S)
    for i in range(S):
        sess.enqueue(trained["src"][i], SEQ)
    assert sorted(sess.admit_pending()) == list(range(S))
    tracing.enable(True)
    try:
        rd = tracing.round_begin()
        assert sess.cancel_many([2, 0, 1]) == [2, 0, 1]
        tracing.round_end(rd)
        rd = tracing.round_begin()
        assert sess.cancel(3) is True
        tracing.round_end(rd)
        batch, lone = tracing.rounds()
    finally:
        tracing.enable(False)
    names = [sp["name"] for sp in batch["spans"]]
    assert names.count("cancel") == 1 and names.count("cancel.dispatch") == 1
    dispatch = batch["spans"][names.index("cancel.dispatch")]
    assert batch["spans"][dispatch["parent"]]["name"] == "cancel"
    root = batch["spans"][0]
    rung = sess._rung_of(3)
    assert (root["cancel_rows"], root["cancel_dispatches"],
            root["cancel_pad_rows"]) == (3, 1, rung - 3)
    root = lone["spans"][0]
    assert (root["cancel_rows"], root["cancel_dispatches"],
            root["cancel_pad_rows"]) == (1, 1, 0)
    assert (sess.release_rows, sess.release_dispatches) == (4, 2)
    assert sess.pool_conserved and sess.free_slots == S


def test_a_round_that_admits_several_holds_one_admit_span(trained):
    """Three queued requests, one worker pass: ONE ``admit`` span with
    ONE ``admit.dispatch`` child (the batch's encoder dispatch),
    ``admit_rows`` the admissions, the rung's other rows counted as
    padding; each request's own trace keeps its ``queue`` and ``prefill``
    spans, which begin and end with the batch."""
    sess = _paged(trained, sampler=None, prefix_cache_pages=0,
                  num_groups=S)
    tracing.enable(True)
    try:
        rd = tracing.round_begin()
        traces = [tracing.start(endpoint="generate") for _ in range(3)]
        for i, tr in enumerate(traces):
            sess.enqueue(trained["src"][i], SEQ, trace_id=tr.id)
        admitted = sess.admit_pending()
        tracing.round_end(rd)
        (rnd,) = tracing.rounds()
    finally:
        tracing.enable(False)
    assert sorted(admitted) == [0, 1, 2]
    names = [sp["name"] for sp in rnd["spans"]]
    assert names.count("admit") == 1 and names.count("admit.dispatch") == 1
    dispatch = rnd["spans"][names.index("admit.dispatch")]
    assert rnd["spans"][dispatch["parent"]]["name"] == "admit"
    root = rnd["spans"][0]
    rung = next(r for r in sess._admit_rungs if r >= 3)
    assert (root["admit_rows"], root["admit_dispatches"],
            root["admit_pad_rows"]) == (3, 1, rung - 3)
    assert (sess.admit_rows, sess.admit_dispatches) == (3, 1)
    for slot, tr in enumerate(traces):
        by_name = {sp["name"]: sp for sp in tr.spans}
        queue, prefill = by_name["queue"], by_name["prefill"]
        assert prefill["meta"]["slot"] == slot
        assert prefill["meta"]["round"] == queue["meta"]["round"] == rnd["id"]
        # the batch's start and end, inside the round's admit span
        admit = rnd["spans"][names.index("admit")]
        assert queue["t1"] == prefill["t0"] >= admit["t0"]
        assert dispatch["t1"] <= prefill["t1"] <= admit["t1"] + 0.005
        tracing.finish(tr)


def test_request_spans_name_a_round_in_the_ring(served):
    ids = {r["id"] for r in served["rounds"]}
    seen = 0
    for rec in served["traces"]:
        for sp in rec["spans"]:
            if sp["name"] in ("prefill", "decode.step", "admit"):
                assert sp["meta"]["round"] in ids, sp
                seen += 1
        at = {sp["name"]: sp["meta"]["round"] for sp in rec["spans"]
              if sp["name"] in ("queue", "prefill")}
        if "queue" in at:
            # queued in the worker's pass that took it off the wire,
            # admitted in that pass or a later one
            assert at["queue"] is not None
            assert at["queue"] <= at["prefill"]
        by_slot = {}
        for sp in rec["spans"]:
            if sp["name"] == "decode.step":
                by_slot.setdefault(sp["meta"]["slot"], []).append(
                    sp["meta"]["round"])
        # consecutive dispatches of a slot fall into later rounds
        for steps in by_slot.values():
            assert steps == sorted(set(steps))
    assert seen >= 5 + 5 * 4


def test_cancelled_stream_leaves_no_open_span(served):
    rec = served["cancelled"]
    assert rec["outcome"] == "cancelled"
    _sweep_ring([rec] + served["traces"])
    assert any(sp["name"] == "decode.step" for sp in rec["spans"])
    cancels = [(r, sp) for r, _i, sp in _round_spans(served["rounds"])
               if sp["name"] == "cancel"]
    assert len(cancels) == 1
    r, sp = cancels[0]
    kids = [c for c in r["spans"]
            if c["parent"] == r["spans"].index(sp)]
    assert [c["name"] for c in kids] == ["cancel.dispatch"]
    assert all(c["t1"] is not None for c in r["spans"])


def test_tracing_off_round_ring_stays_empty(trained, monkeypatch):
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.frontend import ServingFrontend

    tracing.enable(False)
    readings = _count_readings(monkeypatch)
    sess = _paged(trained, sampler=None, prefix_cache_pages=0)
    with ServingFrontend(session=sess) as fe:
        cl = ServingClient(fe.address)
        out = cl.generate_full(trained["src"][0], src_len=SEQ)
        cl.close()
        # the handler thread kept no account of the streamed request
        assert fe._handlers.open == {} and fe._handlers.totals() == (0.0, 0)
    assert out.shape == (1, SEQ)
    assert tracing.rounds() == [] and tracing.round_id() is None
    assert tracing.completed() == []
    assert readings == []


def test_round_spans_are_annotations_with_the_programs_prefix(
        monkeypatch):
    """Each round span is also a TraceAnnotation ``pt:<name>``, opened
    and closed in nesting order; a session driven with no round open
    writes none."""
    log = []

    class Ann(object):
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("in", self.name))

        def __exit__(self, *exc):
            log.append(("out", self.name))

    monkeypatch.setattr(tracing, "TraceAnnotation", Ann)
    with tracing.span("admit"):
        pass
    assert log == []
    rd = tracing.round_begin()
    with tracing.span("admit"):
        with tracing.span(".dispatch"):
            pass
    tracing.round_count("tokens", 4)
    assert tracing.round_id() == rd.id
    tracing.round_end(rd)
    assert tracing.ANNOTATION_PREFIX == "pt:"
    assert log == [("in", "pt:round"), ("in", "pt:admit"),
                   ("in", "pt:admit.dispatch"), ("out", "pt:admit.dispatch"),
                   ("out", "pt:admit"), ("out", "pt:round")]
    (rec,) = tracing.rounds()
    assert [sp["name"] for sp in rec["spans"]] == [
        "round", "admit", "admit.dispatch"]
    assert rec["spans"][0]["tokens"] == 4
    # a pass that moved nothing is dropped, whatever it left open
    idle = tracing.round_begin()
    idle.begin("wait")
    tracing.round_end(idle, keep=False)
    assert len(tracing.rounds()) == 1 and tracing.round_id() is None


def test_trace_step_is_linear_and_takes_no_module_lock(trained,
                                                        monkeypatch):
    """The slot is bound to its Trace at admission: the per-dispatch hook
    looks nothing up under the module lock while the trace is open, and
    page-seconds still integrate."""
    src, src_len = trained["src"], trained["src_len"]
    sess = _paged(trained, sampler=None, prefix_cache_pages=0)
    for i in range(3):
        sess.enqueue(src[i], int(src_len[i]), trace_id=tracing.mint_id())
    sess.admit_pending()
    assert all(isinstance(tr, tracing.Trace)
               for tr in sess._slot_traces.values())
    lookups = []
    real = tracing.inflight_get
    monkeypatch.setattr(tracing, "inflight_get",
                        lambda tid: lookups.append(tid) or real(tid))
    sess.step()
    sess.step()
    assert lookups == []
    for tr in sess._slot_traces.values():
        assert tr.acc["page_seconds"] > 0
        assert sum(sp["name"] == "decode.step" for sp in tr.spans) == 2
    for slot in list(sess.active_slots):
        sess.cancel(slot)
    assert not sess._slot_traces and sess.pool_conserved


def test_baggage_is_gone():
    tr = tracing.start(endpoint="generate")
    rec = tracing.finish(tr)
    assert "baggage" not in rec and not hasattr(tr, "baggage")
    with pytest.raises(TypeError):
        tracing.start(baggage={"k": "v"})
    assert tracing.RING == 4096 and tracing.ROUND_RING >= 1000


def test_trace_view_counts_a_requests_wait_and_decode_in_rounds(
        served, capsys):
    """``round=`` on the request spans has a reader: the waterfall says
    how many passes of the worker a request queued and decoded
    through."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "tools", "trace_view.py"))
    view = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(view)
    solo = [rec for rec in served["traces"]
            if any(sp["name"] == "queue" for sp in rec["spans"])]
    assert len(solo) >= 3
    full = []
    for rec in solo:
        waited, decoded = view._rounds(rec["spans"])
        assert waited is not None and 0 <= waited <= 1
        # a stream runs to max length: four dispatches, four rounds (the
        # cancelled one fewer)
        assert 1 <= decoded <= 4
        full += [rec] if decoded == 4 else []
    assert len(full) >= 3
    view._waterfall(full[0])
    out = capsys.readouterr().out
    assert "queue_rounds=" in out and "decode_rounds=4" in out
    assert " round=" in out
    # spans that fell into no round count nothing
    assert view._rounds([{"name": "queue", "meta": {"round": None}},
                         {"name": "decode.step", "meta": {}}]) \
        == (None, None)
