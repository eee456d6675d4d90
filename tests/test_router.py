"""Router tier: fleet-grade serving (serving/router.py) — one address
over N frontends with prefix-affinity routing, degradation-aware
shedding, heartbeat-leased membership, and zero-loss live session
migration (planned drain + failover from a banked snapshot).

Covers, in order: consistent-ring stability under membership change
(the property ``prefix_hit_rate`` survives scale-out by), the TLS/auth
front door (typed non-retriable ``AuthError``), lease-lapse eviction,
client address rotation, the degradation-aware pick policy, affinity
routing + the ``router.route`` chaos site (an injected fault re-routes
— never surfaces), unary round-robin with degraded shedding, and the
two migration legs against an UNINTERRUPTED oracle: planned drain
mid-stream (snapshot -> ship -> restore -> sever -> re-attach splice,
banked results reclaimable via ``take_result`` through the router) and
failover (frozen + severed victim, restore of its last banked
snapshot on the survivor) — both bit-identical under a top-k sampler
(sampling keys are (seed, slot, position)), zero duplicated and zero
lost tokens, pools conserved on every teardown. The client-side
(rid, seq) splice is covered against a direct frontend too (a
connection blip with ``resume=True``).

Geometry is IDENTICAL to test_frontend.py so the jax executables are
shared through the exec cache across the tier-1 run.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.core import exec_cache
from paddle_tpu.distributed.master import (
    AuthError,
    JsonLineClient,
    close_json_server,
)
from paddle_tpu.executor import global_scope
from paddle_tpu.resilience import chaos
from paddle_tpu.serving.client import ServingClient, StreamBrokenError
from paddle_tpu.serving.server import ServingError
from paddle_tpu.serving.frontend import ServingFrontend
from paddle_tpu.serving.generation import Sampler, SlotDecodeSession
from paddle_tpu.serving.router import (
    ConsistentRing,
    RouterMember,
    ServingRouter,
)
from paddle_tpu.serving.snapshot import DecodeSnapshotManager

VOCAB, SEQ, D, S = 24, 8, 32, 4
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=1,
           n_head=2, d_inner=64)

# source row 5 decodes the full SEQ-1 tokens without an early EOS
# (seeded model + seeded sampler make this stable) — the migration
# legs need a generation long enough to interrupt
LONG_SRC = 5


@pytest.fixture(autouse=True)
def _clean_chaos_and_flags():
    yield
    chaos.disable()
    flags.set_flag("dispatch_retries", 0)


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


def _expected_tokens(oracle, src, src_len=SEQ):
    """The oracle's generated token list: everything after bos up to
    and including the first eos (or the full row)."""
    row = oracle.generate(np.asarray(src)[None, :], [src_len])[0]
    out = []
    for t in row[1:]:
        out.append(int(t))
        if t == 2:
            break
    return out


def _stream_tokens(events):
    toks = []
    for e in events:
        if e["event"] == "tokens":
            toks.extend(int(t) for t in e["tokens"])
    return toks


class _StubFrontend(object):
    """Just enough frontend surface for RouterMember registration."""

    address = ("127.0.0.1", 9)
    _snap_mgr = None


# ---------------------------------------------------------------------------
# the ring: affinity stability under membership change
# ---------------------------------------------------------------------------

def test_ring_affinity_stable_under_membership_change():
    keys = ["req-%d" % i for i in range(300)]
    r3 = ConsistentRing(["a", "b", "c"])
    r4 = ConsistentRing(["a", "b", "c", "d"])
    moved = 0
    for k in keys:
        if r4.pick(k) != r3.pick(k):
            # the consistent-hash contract: a key's owner changes ONLY
            # to the new member — never between survivors
            assert r4.pick(k) == "d", k
            moved += 1
    # ~1/4 of the keyspace moves on 3 -> 4; far from all of it
    assert 0 < moved < len(keys) // 2
    rm = ConsistentRing(["a", "b", "c"])
    rm.remove("b")
    for k in keys:
        if r3.pick(k) != "b":
            assert rm.pick(k) == r3.pick(k), k
        else:
            assert rm.pick(k) in ("a", "c")
    # skip walks clockwise past excluded members, never returns them
    for k in keys[:50]:
        owner = r3.pick(k)
        assert r3.pick(k, skip={owner}) != owner
    assert r3.pick("x", skip={"a", "b", "c"}) is None


# ---------------------------------------------------------------------------
# membership: auth front door, lease lapse, stub members
# ---------------------------------------------------------------------------

def test_auth_front_door_typed_reject_and_member_registration():
    with ServingRouter(lease_s=5.0, health_poll_s=0,
                       auth_token="sesame") as r:
        bad = JsonLineClient(r.address)
        with pytest.raises(AuthError):
            bad._call(method="status")
        bad.close()
        # a wrong token is the same typed, non-retriable reject
        wrong = JsonLineClient(r.address, auth_token="open")
        with pytest.raises(AuthError):
            wrong._call(method="status")
        wrong.close()
        m = RouterMember(_StubFrontend(), r.address,
                         auth_token="sesame")
        try:
            assert m.worker_id in r.stats()["frontends"]
        finally:
            m.close()


def test_lease_lapse_evicts_and_runs_failover():
    with ServingRouter(lease_s=0.3, health_poll_s=0) as r:
        # heartbeat far slower than the lease: the member lapses
        m = RouterMember(_StubFrontend(), r.address, heartbeat_s=30.0)
        wid = m.worker_id
        assert wid in r.stats()["frontends"]
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if wid not in r.stats()["frontends"]:
                break
            time.sleep(0.05)
        st = r.stats()
        assert wid not in st["frontends"]
        # the eviction hook ran the failover; a stub banks no snapshot
        # and owned no streams, so it is a counted no-op
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and not st["failovers"]:
            time.sleep(0.05)
            st = r.stats()
        assert st["failovers"] == 1 and st["lost_streams"] == 0
        m.close(leave=False)


def test_client_rotates_across_dead_addresses():
    with ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        # first address refuses connections: the client must rotate to
        # the live router and answer
        cl = ServingClient([("127.0.0.1", 1), r.address])
        st = cl._request(method="stats")
        assert st["ok"] and "frontends" in st["stats"]
        cl.close()


def test_degradation_aware_pick_policy():
    with ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(_StubFrontend(), r.address)
        m2 = RouterMember(_StubFrontend(), r.address)
        w1, w2 = m1.worker_id, m2.worker_id
        try:
            # shed members stop receiving NEW admissions while a
            # healthy peer exists — for every key
            r._mark_degraded(w1, "shed")
            assert all(r._pick_stream("k%d" % i, set()) == w2
                       for i in range(20))
            # every member degraded: fall back to live (the fleet's
            # typed degradation answer beats a router error)
            r._mark_degraded(w2, "brownout")
            assert r._pick_stream("k", set()) in (w1, w2)
            # draining members are excluded even when the alternative
            # is degraded
            with r._mu:
                r._draining.add(w2)
            assert r._pick_stream("k", set()) == w1
            # nothing routable at all
            with r._mu:
                r._draining.add(w1)
            assert r._pick_stream("k", set()) is None
        finally:
            m1.close()
            m2.close()


# ---------------------------------------------------------------------------
# routing: affinity + chaos re-route, unary round-robin
# ---------------------------------------------------------------------------

def test_generate_affinity_and_route_fault_rerouted(trained):
    src = trained["src"]
    s1, s2, oracle = _paged(trained), _paged(trained), _paged(trained)
    pfx = [int(t) for t in src[0][:5]]
    with ServingFrontend(session=s1) as fe1, \
            ServingFrontend(session=s2) as fe2, \
            ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        m2 = RouterMember(fe2, r.address)
        cl = ServingClient(r.address)
        try:
            want = oracle.generate_best_of(src[0], 1, src_len=SEQ,
                                           prefix_tokens=pfx)
            # the same (src, prefix) twice: the affinity key pins both
            # admissions to ONE member, so the second rides its warm
            # prefix cache — hit rate survives the fleet
            got1 = cl.generate_full(src[0], src_len=SEQ,
                                    prefix_tokens=pfx)
            got2 = cl.generate_full(src[0], src_len=SEQ,
                                    prefix_tokens=pfx)
            assert np.array_equal(got1, want)
            assert np.array_equal(got2, want)
            stats = [s.prefix_cache_stats() for s in (s1, s2)]
            landed = [st for st in stats if st["lookups"]]
            assert len(landed) == 1, stats
            assert landed[0]["lookups"] >= 2 and landed[0]["hits"] >= 1
            # an injected route fault re-routes to the other member —
            # the client never sees it, tokens stay oracle-exact
            # (identical (seed, slot, position) keys on either member)
            chaos.configure("io@site=router.route,n=1")
            got = cl.generate_full(src[1], src_len=5)
            assert chaos.fires("router.route") == 1
            want1 = oracle.generate(src[1][None, :], [5])
            assert np.array_equal(got[0], want1[0])
        finally:
            cl.close()
            m1.close()
            m2.close()


def test_predict_round_robin_and_degraded_shed(trained):
    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    from paddle_tpu.serving import loadgen
    from paddle_tpu.serving.server import BatchingServer
    import tempfile

    model_dir = tempfile.mkdtemp(prefix="router_demo_")
    loadgen.build_demo_model(model_dir, train_steps=5)
    pred = create_paddle_predictor(
        NativeConfig(model_dir=model_dir, use_tpu=False))
    sv1 = BatchingServer(pred, max_batch=8, workers=1,
                         batch_linger_s=0.002)
    sv2 = BatchingServer(pred, max_batch=8, workers=1,
                         batch_linger_s=0.002)
    with sv1, sv2, ServingFrontend(server=sv1) as fe1, \
            ServingFrontend(server=sv2) as fe2, \
            ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        m2 = RouterMember(fe2, r.address)
        cl = ServingClient(r.address)
        try:
            reqs = loadgen.demo_requests(4, seed=5)
            for req in reqs:
                got = cl.predict(req)
                want = sv1.run_reference(req)
                assert all(np.array_equal(g, w)
                           for g, w in zip(got, want))
            n1 = fe1.stats()["requests"]["predict"]["ok"]
            n2 = fe2.stats()["requests"]["predict"]["ok"]
            assert n1 >= 1 and n2 >= 1 and n1 + n2 == 4
            # a degraded member sheds NEW unary admissions to its peer
            r._mark_degraded(m1.worker_id, "shed")
            for req in loadgen.demo_requests(2, seed=9):
                cl.predict(req)
            assert fe1.stats()["requests"]["predict"]["ok"] == n1
            assert fe2.stats()["requests"]["predict"]["ok"] == n2 + 2
        finally:
            cl.close()
            m1.close()
            m2.close()


# ---------------------------------------------------------------------------
# migration: planned drain + failover, bit-exact vs the oracle
# ---------------------------------------------------------------------------

def test_drain_midstream_bit_exact_and_banked_reclaim(
        trained, tmp_path):
    src = trained["src"]
    s1, s2, oracle = _paged(trained), _paged(trained), _paged(trained)
    exp = _expected_tokens(oracle, src[LONG_SRC])
    exp_banked = oracle.generate(src[6][None, :], [SEQ])[0]
    fe1 = ServingFrontend(
        session=s1, snapshot_manager=DecodeSnapshotManager(
            s1, str(tmp_path / "snapA"), interval_steps=1))
    fe2 = ServingFrontend(
        session=s2, snapshot_manager=DecodeSnapshotManager(
            s2, str(tmp_path / "snapB"), interval_steps=1))
    with fe1, fe2, ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)  # registered first: the
        cl = ServingClient(r.address)      # stream lands on fe1
        try:
            # a headless request banks its result on the victim — the
            # migration must carry the bank (enqueue at the worker's
            # quiesce point; direct session calls race the step loop)
            rid_banked = fe1._decode.call(
                lambda: s1.enqueue(src[6], SEQ))
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if rid_banked in s1._results:
                    break
                time.sleep(0.02)
            assert rid_banked in s1._results
            # slow each decode dispatch so the drain lands MID-stream
            chaos.configure("slow@site=serve.dispatch,p=1.0,secs=0.15")
            gen = cl.generate(src[LONG_SRC], src_len=SEQ)
            events = []
            while True:
                ev = next(gen)
                events.append(ev)
                if ev["event"] == "tokens":
                    break
            m2 = RouterMember(fe2, r.address)
            cl2 = ServingClient(r.address)
            res = cl2._request(method="drain", worker_id=m1.worker_id)
            assert res["ok"] and res["target"] == m2.worker_id
            # the drain caught the generation LIVE and the bank rode
            # along
            assert res["live"], res
            assert rid_banked in res["banked"]
            events.extend(gen)
            chaos.disable()
            # spliced stream: bit-identical to the uninterrupted
            # oracle, no duplicated and no dropped tokens
            assert _stream_tokens(events) == exp
            st = r.stats()
            assert st["migrations"] == 1 and st["lost_streams"] == 0
            assert st["migration_seconds"]
            # the banked result is claimable THROUGH the router, off
            # the migration target
            got_banked = cl2.take_result(rid_banked)
            assert np.array_equal(got_banked, exp_banked)
            # the drained member is pinned out of routing even though
            # its heartbeats re-register it under the same id
            n_before = len(s1._results)
            got_after = cl2.generate_full(src[1], src_len=5)
            want_after = oracle.generate(src[1][None, :], [5])
            assert np.array_equal(got_after[0], want_after[0])
            assert len(s1._results) == n_before
            assert not s1.active_slots and not s1.pending_requests
            # teardown conservation on both pools
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not (
                    s1.pool_conserved and s2.pool_conserved
                    and not s2.active_slots):
                time.sleep(0.02)
            assert s1.pool_conserved and s2.pool_conserved
            cl2.close()
            m2.close()
        finally:
            chaos.disable()
            cl.close()
            m1.close()


def test_failover_restores_banked_snapshot_bit_exact(
        trained, tmp_path):
    src = trained["src"]
    s1, s2, oracle = _paged(trained), _paged(trained), _paged(trained)
    exp = _expected_tokens(oracle, src[LONG_SRC])
    compiled = exec_cache.stats()["fresh_compiles"]
    fe1 = ServingFrontend(
        session=s1, snapshot_manager=DecodeSnapshotManager(
            s1, str(tmp_path / "snapA"), interval_steps=1))
    fe2 = ServingFrontend(
        session=s2, snapshot_manager=DecodeSnapshotManager(
            s2, str(tmp_path / "snapB"), interval_steps=1))
    unfreeze = threading.Event()
    with fe2, ServingRouter(lease_s=1.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        cl = ServingClient(r.address)
        try:
            chaos.configure("slow@site=serve.dispatch,p=1.0,secs=0.15")
            gen = cl.generate(src[LONG_SRC], src_len=SEQ)
            events, ntok = [], 0
            while ntok < 2:
                ev = next(gen)
                events.append(ev)
                if ev["event"] == "tokens":
                    ntok += len(ev["tokens"])
            m2 = RouterMember(fe2, r.address)
            # "kill" fe1 without a subprocess: freeze its decode loop
            # at the next quiesce point (no further snapshots — like a
            # SIGKILL, the last BANKED snapshot is the failover basis),
            # stop its heartbeats, sever its server
            with pytest.raises(TimeoutError):
                fe1._decode.call(lambda: unfreeze.wait(30.0),
                                 timeout=0.0)
            m1._stop.set()
            close_json_server(fe1._json_server)
            fe1._json_server = None
            t0 = time.monotonic()
            events.extend(gen)
            chaos.disable()
            # the severed relay + failed probe detect the death FAST —
            # well inside the migration budget, no lease wait needed
            assert time.monotonic() - t0 < 30.0
            assert _stream_tokens(events) == exp
            st = r.stats()
            assert st["failovers"] == 1 and st["migrations"] == 1
            assert st["lost_streams"] == 0
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not (
                    s2.pool_conserved and not s2.active_slots):
                time.sleep(0.02)
            assert s2.pool_conserved
            # the survivor served the restore from executables it had
            assert exec_cache.stats()["fresh_compiles"] == compiled
            m2.close()
        finally:
            chaos.disable()
            unfreeze.set()
            cl.close()
            m1.close(leave=False)
            fe1.close()


# ---------------------------------------------------------------------------
# client-side splice: resume=True, attached DIRECTLY to the victim
# ---------------------------------------------------------------------------

def test_client_resume_rotates_to_router_after_victim_death(
        trained, tmp_path):
    """A client streaming directly from a frontend (router only in its
    fallback address list) survives that frontend's death: the sever
    triggers the resume path, the client rotates to the router, and
    the router — seeing a rid it never relayed, owned by an
    unreachable member — runs the failover, restores the banked
    snapshot on the survivor, and re-drives the attach. The client's
    own (rid, seq) splice trims the replay."""
    src = trained["src"]
    s1, s2, oracle = _paged(trained), _paged(trained), _paged(trained)
    exp = _expected_tokens(oracle, src[LONG_SRC])
    fe1 = ServingFrontend(
        session=s1, snapshot_manager=DecodeSnapshotManager(
            s1, str(tmp_path / "snapA"), interval_steps=1))
    fe2 = ServingFrontend(
        session=s2, snapshot_manager=DecodeSnapshotManager(
            s2, str(tmp_path / "snapB"), interval_steps=1))
    unfreeze = threading.Event()
    with fe2, ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        m2 = RouterMember(fe2, r.address)
        cl = ServingClient([fe1.address, r.address])
        try:
            chaos.configure("slow@site=serve.dispatch,p=1.0,secs=0.15")
            gen = cl.generate(src[LONG_SRC], src_len=SEQ, resume=True)
            events, ntok = [], 0
            while ntok < 2:
                ev = next(gen)
                events.append(ev)
                if ev["event"] == "tokens":
                    ntok += len(ev["tokens"])
            # kill the victim under its direct client
            with pytest.raises(TimeoutError):
                fe1._decode.call(lambda: unfreeze.wait(30.0),
                                 timeout=0.0)
            m1._stop.set()
            close_json_server(fe1._json_server)
            fe1._json_server = None
            events.extend(gen)
            chaos.disable()
            assert _stream_tokens(events) == exp
            st = r.stats()
            assert st["failovers"] == 1 and st["lost_streams"] == 0
            m2.close()
        finally:
            chaos.disable()
            unfreeze.set()
            cl.close()
            m1.close(leave=False)
            fe1.close()


# ---------------------------------------------------------------------------
# rid namespaces: per-member ids must never cross-resolve
# ---------------------------------------------------------------------------

def test_take_result_rid_collision_resolves_to_minting_member(
        trained, tmp_path):
    """Two frontends mint the SAME rid number for different requests
    (rids are per-member namespaces counting from 0). The router's
    composite "wid:rid" handle claims exactly the minting member's
    result; a bare ambiguous rid is a typed miss (None) — it must
    never pop another member's bank."""
    src = trained["src"]
    s1, s2, oracle = _paged(trained), _paged(trained), _paged(trained)
    exp6 = oracle.generate(src[6][None, :], [SEQ])[0]
    exp7 = oracle.generate(src[7][None, :], [SEQ])[0]
    fe1, fe2 = ServingFrontend(session=s1), ServingFrontend(session=s2)
    with fe1, fe2, ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        m2 = RouterMember(fe2, r.address)
        cl = ServingClient(r.address)
        try:
            rid1 = fe1._decode.call(lambda: s1.enqueue(src[6], SEQ))
            rid2 = fe2._decode.call(lambda: s2.enqueue(src[7], SEQ))
            # the collision premise: independent namespaces, same number
            assert rid1 == rid2
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline and not (
                    rid1 in s1._results and rid2 in s2._results):
                time.sleep(0.02)
            assert rid1 in s1._results and rid2 in s2._results
            # a BARE rid with two live members is ambiguous: typed
            # miss, both banks untouched
            assert cl.take_result(rid1) is None
            assert rid1 in s1._results and rid2 in s2._results
            # composite handles resolve to exactly their namespace
            got1 = cl.take_result("%s:%d" % (m1.worker_id, rid1))
            assert np.array_equal(got1, exp6)
            assert rid2 in s2._results  # fe2's bank survived the claim
            got2 = cl.take_result("%s:%d" % (m2.worker_id, rid2))
            assert np.array_equal(got2, exp7)
        finally:
            cl.close()
            m1.close()
            m2.close()


def test_drain_failure_rolls_back_routing_pin(trained, tmp_path):
    """A drain that cannot land (here: no surviving target) raises its
    typed error AND unpins the victim — one transient failure must not
    remove a healthy frontend from routing forever."""
    src = trained["src"]
    s1, oracle = _paged(trained), _paged(trained)
    fe1 = ServingFrontend(
        session=s1, snapshot_manager=DecodeSnapshotManager(
            s1, str(tmp_path / "snapA"), interval_steps=1))
    with fe1, ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        cl = ServingClient(r.address)
        try:
            with pytest.raises(ServingError):
                cl._request(method="drain", worker_id=m1.worker_id)
            st = r.stats()
            assert st["frontends"][m1.worker_id]["draining"] is False
            # the member still serves: the failed drain left no pin
            got = cl.generate_full(src[1], src_len=5)
            want = oracle.generate(src[1][None, :], [5])
            assert np.array_equal(got[0], want[0])
        finally:
            cl.close()
            m1.close()


# ---------------------------------------------------------------------------
# relay discipline: in-band cancel while the upstream is producing,
# typed loss for rid-less (group) streams
# ---------------------------------------------------------------------------

def test_inband_cancel_propagates_while_upstream_producing(trained):
    """The relay polls the downstream on EVERY event, so a mid-stream
    cancel reaches the member while tokens are still flowing — the
    generation is torn down instead of running to completion."""
    src = trained["src"]
    s1 = _paged(trained)
    fe1 = ServingFrontend(session=s1)
    with fe1, ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        cl = ServingClient(r.address)
        try:
            chaos.configure("slow@site=serve.dispatch,p=1.0,secs=0.3")
            gen = cl.generate(src[LONG_SRC], src_len=SEQ)
            while next(gen)["event"] != "tokens":
                pass
            gen.close()  # sends the in-band cancel and drains the ack
            chaos.disable()
            # the frontend saw the teardown mid-flight: its generate
            # stream must NOT have completed normally
            deadline = time.monotonic() + 10.0
            outcomes = {}
            while time.monotonic() < deadline:
                outcomes = fe1.stats()["requests"].get("generate", {})
                if outcomes and not s1.active_slots:
                    break
                time.sleep(0.05)
            assert outcomes.get("ok", 0) == 0, outcomes
            assert not s1.active_slots
            assert s1.pool_conserved
        finally:
            chaos.disable()
            cl.close()
            m1.close()


def test_group_stream_sever_after_delivery_is_typed_loss(trained):
    """Fork-group streams carry no rid (the frontend attaches no id to
    their events), so a sever after delivery cannot re-attach: the
    router must answer with a TYPED StreamBrokenError and count the
    lost stream — never an untyped internal error."""
    src = trained["src"]
    s1 = _paged(trained)
    fe1 = ServingFrontend(session=s1)
    with ServingRouter(lease_s=5.0, health_poll_s=0) as r:
        m1 = RouterMember(fe1, r.address)
        cl = ServingClient(r.address)
        try:
            chaos.configure("slow@site=serve.dispatch,p=1.0,secs=0.3")
            gen = cl.generate(src[LONG_SRC], src_len=SEQ, n=2)
            while next(gen)["event"] != "tokens":
                pass
            # kill the member's server under the live relay
            close_json_server(fe1._json_server)
            fe1._json_server = None
            with pytest.raises(StreamBrokenError):
                for _ in gen:
                    pass
            chaos.disable()
            assert r.stats()["lost_streams"] == 1
        finally:
            chaos.disable()
            cl.close()
            m1.close(leave=False)
            fe1.close()


# ---------------------------------------------------------------------------
# resumed events carry bos (the router's synthesized-admission basis)
# ---------------------------------------------------------------------------

def test_resumed_events_carry_bos(trained):
    """Every ``resumed`` variant must carry ``bos`` — the router
    synthesizes an admission from it when a stream fails over before
    its admission event reached the client; a missing field silently
    corrupted non-zero-bos sessions' first prefix token."""
    src = trained["src"]
    s1 = _paged(trained)
    fe1 = ServingFrontend(session=s1)
    with fe1:
        # banked: a headless request finishes into the result bank
        rid = fe1._decode.call(lambda: s1.enqueue(src[6], SEQ))
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and rid not in s1._results:
            time.sleep(0.02)
        assert rid in s1._results
        cl = ServingClient(fe1.address)
        try:
            cl._send_line({"method": "attach", "id": int(rid)})
            first = cl._recv_line()
            assert first["event"] == "resumed" and first["finished"]
            assert first["bos"] == int(s1._bos)
            assert cl._recv_line()["event"] == "end"
            # live: attach to a mid-flight headless generation
            chaos.configure("slow@site=serve.dispatch,p=1.0,secs=0.2")
            rid2 = fe1._decode.call(lambda: s1.enqueue(src[5], SEQ))
            deadline = time.monotonic() + 30.0
            while (time.monotonic() < deadline
                    and rid2 not in s1._owner.values()):
                time.sleep(0.02)
            assert rid2 in s1._owner.values()
            cl2 = ServingClient(fe1.address)
            cl2._send_line({"method": "attach", "id": int(rid2)})
            first2 = cl2._recv_line()
            assert first2["event"] == "resumed"
            assert not first2["finished"]
            assert first2["bos"] == int(s1._bos)
            cl2.close()  # disconnect cancels the attached generation
            chaos.disable()
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and s1.active_slots:
                time.sleep(0.02)
            assert s1.pool_conserved
        finally:
            chaos.disable()
            cl.close()
