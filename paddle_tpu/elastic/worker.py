"""ElasticTrainSession: a training loop that survives fleet churn.

PR 5's :class:`~paddle_tpu.resilience.session.TrainSession` survives the
*machine* (preemption, crash, hang); this wrapper makes it survive the
*fleet*: it registers with a :class:`~paddle_tpu.elastic.coordinator.
FleetCoordinator`, heartbeats on a daemon thread, and treats a
membership-generation change as a first-class training event. Every
``run()`` starts with a **step barrier**:

1. the cached heartbeat view is compared against the generation this
   session was built for — a mismatch means the fleet reshaped while
   the last step was in flight;
2. the chief of the new membership (rank 0) finishes holding consistent
   state, so it writes a synchronous **sharded** checkpoint
   (``reshard.ShardedCheckpointManager`` — var files laid out by the
   OLD mesh's plan) and publishes ``(generation, serial)`` through
   ``report_reshard``;
3. every member tears down its executor, rebuilds mesh + executor at
   the new world size via the user's ``build_fn(world_size, rank)``,
   and **reshard-restores** the published serial — shard files
   reassembled to full host arrays, RNG stream (base seed + run
   counter) restored, step counter taken from the manifest — then
   keeps training. ``paddle_tpu_reshard_seconds`` times the whole
   rebuild.

Because restore re-seats both state and the RNG stream, the loss
trajectory after a reshape is *bit-identical* to a fresh process
restored from the same checkpoint at that world size — the contract
``tests/test_elastic.py`` asserts through a join and an eviction
(``test_elastic_session_reshapes_with_bit_identical_trajectory``).

A worker that was evicted (it stalled past its lease; heartbeats answer
``unknown_worker``) re-registers as a *new* member and rejoins at the
next generation — same path a brand-new worker takes. Coordinator RPC
failures are classified by ``resilience.retry`` (the shared
JsonLineClient reconnect-retry contract): a coordinator restart is a
transient blip, an eviction is a typed signal, never a hang.

``build_fn(world_size, rank)`` returns ``(executor, main_program)`` or
``(executor, main_program, scope)`` with the startup program already
run. The executor may be a plain ``Executor`` (factors stay empty, vars
land as single files) or a ``ParallelExecutor`` whose planning mesh is
sized to ``world_size`` — its derived ``sharding_plan()`` lays out the
shard files. Tensor-parallel plans raise
:class:`~paddle_tpu.elastic.reshard.ReshardError` at build time (dim-0
resharding only — the documented elastic-data-parallel-first scope).
"""

import os
import threading
import time

from paddle_tpu.elastic.coordinator import (
    FleetClient,
    FleetEvictedError,
    _fleet_generation,
    _fleet_size,
)
from paddle_tpu.elastic.reshard import (
    ShardedCheckpointManager,
    _reshard_seconds,
)
from paddle_tpu.resilience.session import TrainSession

__all__ = ["ElasticTrainSession", "session_executor"]


class _MeshExecutorFacade(object):
    """Adapts a ParallelExecutor to the Executor calling convention
    TrainSession and CheckpointManager expect: ``run(program, feed=...,
    fetch_list=..., scope=...)`` (the PE owns its program and scope, so
    both are accepted and ignored) and the ``_base_seed``/
    ``_run_counter`` RNG surface proxied through so checkpoint capture
    AND restore hit the real executor."""

    def __init__(self, pe):
        self._pe = pe

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            **kwargs):
        return self._pe.run(fetch_list=fetch_list, feed=feed, **kwargs)

    @property
    def _base_seed(self):
        return self._pe._base_seed

    @_base_seed.setter
    def _base_seed(self, v):
        self._pe._base_seed = v

    @property
    def _run_counter(self):
        return self._pe._run_counter

    @_run_counter.setter
    def _run_counter(self, v):
        self._pe._run_counter = v


def session_executor(exe):
    """The executor object TrainSession should drive: ParallelExecutors
    (anything carrying a ``mesh``) get the facade, plain Executors pass
    through."""
    return _MeshExecutorFacade(exe) if hasattr(exe, "mesh") else exe


class _GenerationMoved(Exception):
    """Internal: membership changed again while a barrier was waiting —
    restart the rebuild against the newer view."""

    def __init__(self, view):
        self.view = view
        super(_GenerationMoved, self).__init__()


class _HeartbeatThread(threading.Thread):
    """Daemon lease-keeper: one heartbeat per interval, last good
    membership view cached for the step barrier to read lock-free (the
    dict swap is atomic under the GIL). Transport errors are tolerated
    (the coordinator may be mid-restart — the next beat retries); an
    eviction is latched for the main thread to act on."""

    def __init__(self, addr, worker_id, interval_s):
        super(_HeartbeatThread, self).__init__(
            name="paddle-tpu-fleet-heartbeat", daemon=True)
        self._addr = addr
        self._interval_s = float(interval_s)
        self._stop = threading.Event()
        self._worker_id = worker_id
        self.latest = None
        self.evicted = False
        self.step = 0

    def set_worker(self, worker_id, view=None):
        self._worker_id = worker_id
        self.evicted = False
        if view is not None:
            self.latest = view

    def run(self):
        client = FleetClient(self._addr)
        try:
            while not self._stop.wait(self._interval_s):
                if self.evicted:
                    continue  # main thread re-registers, then un-latches
                try:
                    view = client.heartbeat(self._worker_id, step=self.step)
                except FleetEvictedError:
                    self.evicted = True
                except Exception:  # noqa: BLE001 - transient transport blip
                    continue
                else:
                    self.latest = view
                    # worker-side mirror of the coordinator gauges: a
                    # worker's metrics scrape shows the fleet state it
                    # is acting on
                    _fleet_generation.set(int(view["generation"]))
                    _fleet_size.set(int(view["world"]))
        finally:
            client.close()

    def stop(self):
        self._stop.set()


class ElasticTrainSession(object):
    def __init__(self, coordinator_addr, checkpoint_dir, build_fn,
                 worker_id=None, heartbeat_interval_s=0.5,
                 ready_timeout_s=60.0, barrier_timeout_s=60.0,
                 interval_steps=None, interval_secs=None,
                 max_to_keep=None, session_kwargs=None):
        self._addr = coordinator_addr
        self._client = FleetClient(coordinator_addr)
        self._build_fn = build_fn
        self.checkpoint_dir = str(checkpoint_dir)
        self._interval_steps = interval_steps
        self._interval_secs = interval_secs
        self._max_to_keep = max_to_keep
        self._session_kwargs = dict(session_kwargs or {})
        self._barrier_timeout_s = float(barrier_timeout_s)
        self._closed = False
        self._session = None
        self._exe = None
        self._program = None
        self._scope = None
        self._published = None  # (generation, serial) this worker reported
        self.reshapes = []  # [{generation, world, rank, serial, step}]

        view = self._client.register(worker_id)
        self.worker_id = view["worker_id"]
        self._hb = _HeartbeatThread(coordinator_addr, self.worker_id,
                                    heartbeat_interval_s)
        self._hb.latest = view
        self._hb.start()
        try:
            view = self._wait_ready(view, ready_timeout_s)
            self._apply_view(view)
            self._rebuild(view)
        except BaseException:
            # a failed construction (fleet never ready, an unreshardable
            # tp plan from build_fn, a missing barrier serial) must not
            # leave the heartbeat daemon renewing a zombie member's
            # lease forever — deregister and surface the error
            self._hb.stop()
            try:
                self._client.leave(self.worker_id)
            except Exception:  # noqa: BLE001 - coordinator may be gone
                pass
            self._client.close()
            raise

    # -- membership plumbing -------------------------------------------------

    def _wait_ready(self, view, timeout_s):
        """Block until the fleet holds the coordinator's min_workers;
        return the freshest view (membership may have grown while we
        waited — build once, at the composition that is actually there)."""
        deadline = time.monotonic() + float(timeout_s)
        while not view.get("ready"):
            if time.monotonic() > deadline:
                raise TimeoutError(
                    "fleet not ready after %.0fs (world=%d < min_workers)"
                    % (timeout_s, view.get("world", 0)))
            time.sleep(0.05)
            view = self._hb.latest or view
        return self._hb.latest or view

    def _apply_view(self, view):
        self.generation = int(view["generation"])
        self.world_size = int(view["world"])
        self.rank = int(view["rank"])

    @property
    def is_chief(self):
        return self.rank == 0

    @property
    def step(self):
        return self._session.step if self._session is not None else 0

    # -- the step ------------------------------------------------------------

    def run(self, feed=None, fetch_list=None, **kwargs):
        """One training step. The barrier first: act on any membership
        change the heartbeat thread has seen (the in-flight step that
        was running when the generation changed has already finished —
        run() is only ever between steps)."""
        if self._closed:
            raise RuntimeError("ElasticTrainSession is closed")
        try:
            self._step_barrier()
        except BaseException:
            # a failed reshape (build_fn error, unloadable serial,
            # barrier timeout) must not leave this worker as a lease-
            # renewing zombie — were it the new chief, no serial would
            # ever be published and the whole fleet would wedge behind
            # a member that looks alive. Deregister loudly, then raise.
            self.close(save=False)
            raise
        out = self._session.run(feed=feed, fetch_list=fetch_list, **kwargs)
        self._hb.step = self._session.step
        return out

    def _step_barrier(self):
        if self._hb.evicted:
            self._rejoin()
            return
        view = self._hb.latest
        if view is not None and int(view["generation"]) != self.generation:
            self._reshape(view)

    def _register_fresh(self):
        """Re-admission after an eviction: register under a NEW identity
        (the fleet treats us exactly like a fresh worker joining), point
        the heartbeat thread at it and un-latch the eviction flag."""
        from paddle_tpu.observability import blackbox

        if blackbox.ENABLED:
            blackbox.record("fleet_rejoin", old_worker_id=self.worker_id,
                            step=self.step)
        view = self._client.register()
        self.worker_id = view["worker_id"]
        self._hb.set_worker(self.worker_id, view)
        return view

    def _rejoin(self):
        """We were evicted (a stall outlived the lease): our membership
        is gone, our state is not. Rejoin and reshape into whatever
        generation that admission creates."""
        self._reshape(self._register_fresh())

    def _reshape(self, view):
        """The generation changed: bank state (chief), tear down, rebuild
        at the new world size, reshard-restore, continue."""
        old = (self.generation, self.world_size)
        if int(view.get("rank", -1)) == 0 and self._session is not None:
            # the new membership's chief owns the barrier checkpoint: its
            # live state IS the fleet's state (every member trained the
            # same trajectory), banked sync + sharded under the OLD plan
            serial = self._session.step
            from paddle_tpu.resilience.checkpoint import complete_serials

            # never rewrite an existing serial (back-to-back reshapes
            # with no steps in between): the state at a given step is
            # unique along the bit-exact trajectory, and an in-place
            # rewrite would yank the dir out from under a previous
            # generation's member still mid-restore of it
            if serial not in complete_serials(self.checkpoint_dir):
                self._session.save(final=True)
            self._client.report_reshard(int(view["generation"]), serial)
            # remembered locally too: the heartbeat view _rebuild reads
            # may predate our own report, and re-discovering the serial
            # from disk would re-verify the whole checkpoint for nothing
            self._published = (int(view["generation"]), serial)
        if self._session is not None:
            self._session.close(save=False)
            self._session = None
        self._exe = None
        self._apply_view(view)
        from paddle_tpu.observability import blackbox

        if blackbox.ENABLED:
            blackbox.record(
                "fleet_reshape", old_generation=old[0], old_world=old[1],
                generation=self.generation, world=self.world_size,
                rank=self.rank)
        self._rebuild(view)

    # -- build / restore -----------------------------------------------------

    def _rebuild(self, view):
        """Build executor + mesh at the current world size and restore
        the generation's published serial (chief publishes it if nobody
        has). Timed end to end by ``paddle_tpu_reshard_seconds`` — this
        IS the reshard cost a reshape pays."""
        t0 = time.perf_counter()
        built = self._build_fn(self.world_size, self.rank)
        if len(built) == 2:
            exe, program = built
            scope = None
        else:
            exe, program, scope = built
        self._exe, self._program, self._scope = exe, program, scope
        plan = None
        if hasattr(exe, "sharding_plan"):
            plan = exe.sharding_plan()
        exe = session_executor(exe)
        manager = ShardedCheckpointManager(
            self.checkpoint_dir, plan=plan, executor=exe,
            main_program=program, scope=scope,
            max_to_keep=self._max_to_keep)
        try:
            serial, manifest = self._generation_serial(view, manager)
        except _GenerationMoved as moved:
            # the fleet reshaped again while this barrier waited: the
            # executor we just built is sized for a stale world — rebuild
            # against the membership that is actually there
            self._apply_view(moved.view)
            return self._rebuild(moved.view)
        if manifest is None:
            manifest = manager.restore(serial=serial)
        if manifest is None and serial is not None:
            raise RuntimeError(
                "reshard restore failed: published serial %d for "
                "generation %d is not loadable from %s"
                % (serial, self.generation, self.checkpoint_dir))
        # pin the barrier serial on the manager that prunes from now on:
        # periodic saves must never delete it while a slow joiner may
        # still be restoring it (pin rotates at the next reshape)
        manager.pinned_serials.add(int(serial))
        step = int(manifest.get("step", 0)) if manifest else 0
        # non-chief members never write into the shared checkpoint dir:
        # periodic checkpointing is the chief's duty
        session = TrainSession(
            exe, self.checkpoint_dir, main_program=program, scope=scope,
            manager=manager, auto_resume=False,
            interval_steps=self._interval_steps if self.is_chief else 0,
            interval_secs=self._interval_secs if self.is_chief else 0,
            **self._session_kwargs)
        session.step = step
        session._last_save_step = step
        self._session = session
        self._hb.step = step
        self.reshapes.append({
            "generation": self.generation, "world": self.world_size,
            "rank": self.rank, "serial": serial, "step": step,
        })
        _reshard_seconds.observe(time.perf_counter() - t0)

    def _generation_serial(self, view, manager):
        """``(serial, manifest-or-None)`` for this generation: the
        checkpoint serial it restores from, plus the loaded manifest
        when this call already performed the restore (so the caller
        skips a second verify+load of the same serial). The chief
        publishes a serial if the map has none (cold start): the newest
        verified serial is published as-is — never rewritten, a joiner
        may be mid-restore of that very dir — and with no history at
        all the freshly-initialized state is banked as serial 0. Either
        way every member restores the SAME bytes. Non-chiefs poll the
        heartbeat view until the serial appears; a generation that
        moves again mid-wait (or an eviction latched by the heartbeat
        thread) raises :class:`_GenerationMoved` so the caller rebuilds
        against the live membership."""
        serial = (view.get("reshard") or {}).get(self.generation)
        if serial is not None:
            return int(serial), None
        if self._published and self._published[0] == self.generation:
            return self._published[1], None  # reported at the barrier
        if self.is_chief:
            # genuine cold start: ONE restore pass does it all — the
            # manager's normal newest-verified scan (quarantine + fall
            # back) loads state and RNG into the scope, and the loaded
            # manifest is handed back so _rebuild skips the second
            # restore of the same serial; only a truly empty dir banks
            # the freshly-initialized state as serial 0
            manifest = manager.restore()
            if manifest is not None:
                serial = int(manifest["serial"])
            else:
                manager.save(0, serial=0)
                serial = 0
                # the scope already IS this state (we just wrote it from
                # there); a synthetic manifest skips re-reading it
                manifest = {"serial": 0, "step": 0}
            self._client.report_reshard(self.generation, serial)
            self._published = (self.generation, serial)
            return serial, manifest
        deadline = time.monotonic() + self._barrier_timeout_s
        while time.monotonic() < deadline:
            if self._hb.evicted:
                # evicted mid-barrier (e.g. the coordinator recovered a
                # snapshot predating our registration): the cached view
                # is frozen and will never deliver the serial — rejoin
                # as a new member and rebuild into THAT generation
                raise _GenerationMoved(self._register_fresh())
            latest = self._hb.latest or view
            if int(latest["generation"]) != self.generation:
                raise _GenerationMoved(latest)
            serial = (latest.get("reshard") or {}).get(self.generation)
            if serial is not None:
                return int(serial), None
            time.sleep(0.05)
        raise TimeoutError(
            "no reshard serial published for generation %d within %.0fs"
            % (self.generation, self._barrier_timeout_s))

    # -- lifecycle -------------------------------------------------------------

    def save(self, final=True):
        """Explicit checkpoint at the current step (chief's shared-dir
        discipline is the caller's concern here)."""
        return self._session.save(final=final)

    def close(self, save=True, leave=True):
        """Final checkpoint (chief only — non-chiefs never write the
        shared dir), deregister, stop the heartbeat."""
        if self._closed:
            return
        self._closed = True
        self._hb.stop()
        if self._session is not None:
            self._session.close(save=save and self.is_chief)
            self._session = None
        if leave:
            try:
                self._client.leave(self.worker_id)
            except Exception:  # noqa: BLE001 - coordinator may be gone
                pass
        self._client.close()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close(save=exc_type is None)
        return False
