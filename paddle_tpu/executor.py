"""Executor: run Programs on a Place — by whole-program XLA compilation.

Reference parity: python/paddle/fluid/executor.py:374 (Executor.run feeds
numpy -> tensors, fetches back) + paddle/fluid/framework/executor.cc:163.
The TPU-first difference: instead of a sequential per-op interpreter loop
(executor.cc:392-404), ``run`` traces block 0 through the op lowerings into
one JAX function, jit-compiles it per (program version, feed shapes, fetch
set) — cached like the reference's ``use_program_cache`` — and executes a
single fused XLA program per step. Persistable vars (params, optimizer
state, BN stats) live in the Scope as device arrays and are threaded
through the step function with buffer donation (in-place semantics without
mutation).
"""

import contextlib
import time
import weakref
from collections import OrderedDict, namedtuple

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import framework
from paddle_tpu import profiler as _profiler
from paddle_tpu.core import exec_cache
from paddle_tpu.observability import blackbox as _blackbox
from paddle_tpu.observability import lock_witness as _lock_witness
from paddle_tpu.resilience import chaos as _chaos
from paddle_tpu.resilience import retry as _retry
from paddle_tpu.observability import explain as _explain
from paddle_tpu.observability import memory as _memory
from paddle_tpu.observability import step_profiler as _stepprof
from paddle_tpu.observability import telemetry as _telemetry
from paddle_tpu.core.fingerprint import (
    executable_key,
    program_fingerprint,
    trace_flags_key,
)
from paddle_tpu.core.lod import LoDTensor
from paddle_tpu.core.lowering import (
    CompiledProgram,
    MultiStepProgram,
    step_key,
    step_key_value,
)
from paddle_tpu.core.scope import Scope, ScopeVariable
from paddle_tpu.core.types import Place, TPUPlace, np_dtype

_global_scope = Scope()
_scope_stack = [_global_scope]

# Process-global executable registry, of both executors. Keys are
# content-addressed (core/fingerprint.py), so structurally identical
# programs share ONE compile across Executor instances, scopes with
# identical var-name signatures, and Predictor.Clone() serving threads —
# where the old id(program)/id(scope) keys forced a recompile per instance
# (and could alias a dead program's reused id() to a live one after GC).
# A ParallelExecutor's keys carry its mesh's devices and every policy
# input, so one REBUILT over the same devices (the elastic runtime rebuilds
# per membership generation) reuses the sharded executable. LRU-bounded:
# eviction drops only the shared handle; executors that already hold an
# entry in their instance cache keep using it.
_shared_executables = OrderedDict()
_shared_lock = _lock_witness.make_lock("executor.shared_executables")
_SHARED_CAP = 128

# What tells one entry point from another inside ``_run_step``: the dispatch
# record's and telemetry's ``origin``, the ``name`` the black box, the
# watchdog and the verifier see, the ``dispatch`` origin of a retry or an
# OOM, and the profiler's ``span``.
_Entry = namedtuple("_Entry", "origin name dispatch span")
_RUN = _Entry("single", "Executor.run", "Executor.dispatch", "executor.run")
_RUN_ASYNC = _Entry("async", "Executor.run_async", "Executor.dispatch",
                    "executor.dispatch")
_RUN_MULTI = _Entry("multi_step", "Executor.run_multi_step",
                    "Executor.run_multi_step", "executor.run_multi_step[%d]")
_RUN_PARALLEL = _Entry("parallel", "ParallelExecutor.run",
                       "ParallelExecutor.dispatch", "parallel_executor.run")

# An executable's mode, part of its key: one step, or
# ``("multi", steps, stack_fetches)`` for a scan of ``steps``.
_SINGLE = ("single",)


def global_scope():
    """The scope Executor.run defaults to. Like the reference's
    ``fluid.global_scope()`` / ``scope_guard`` pair (executor.py:g_scope),
    ``scope_guard`` swaps what this returns for the duration of the guard."""
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


def _as_feed_array(value):
    """numpy / LoDTensor / device array -> array. Device arrays (a
    double-buffered PyReader's prefetched feeds) pass through untouched —
    np.asarray would block on the in-flight transfer and round-trip the
    data through the host."""
    if isinstance(value, LoDTensor):
        # .numpy() IS the backing ndarray; re-wrapping it in np.asarray
        # added a per-feed copy whenever the holder wasn't already a plain
        # contiguous ndarray — pass it through untouched instead
        return value.numpy()
    if isinstance(value, jax.Array):
        return value
    return np.asarray(value)


def _fetch_names(fetch_list):
    return [v.name if isinstance(v, framework.Variable) else str(v)
            for v in fetch_list]


def _materialize_fetches(arrays, origin, to_numpy=np.asarray):
    """Host-materialize fetched device arrays. With async dispatch the
    allocator's RESOURCE_EXHAUSTED often surfaces at the first host read
    rather than inside the dispatch call, so every materialize site —
    the sync return of any entry point, FetchHandle.result — routes
    through the same M001 enrichment as the dispatch path."""
    try:
        return [to_numpy(a) for a in arrays]
    except Exception as exc:
        if _memory.is_oom(exc) and not isinstance(
                exc, _memory.MemoryExhaustedError):
            _memory.enrich_and_raise(exc, origin=origin)
        raise


def _maybe_verify(program, feed_specs, fetch_names, origin):
    """FLAGS_verify_program gate: run the structural verifier with the
    concrete feed shapes (resolving deferred shape inference) before a
    fresh compile. Raises analysis.ProgramVerifyError on error-severity
    findings; warnings go to the analysis logger."""
    from paddle_tpu import flags as _flags

    if not _flags.get("verify_program"):
        return
    import logging

    from paddle_tpu.analysis import check_program

    diags = check_program(
        program, level="error", fetch_names=fetch_names,
        feed_shapes={n: s for n, (s, _d) in feed_specs.items()},
        origin=origin)
    if diags:
        logging.getLogger("paddle_tpu.analysis").info(
            "verify (%s): %d non-error diagnostic(s): %s", origin,
            len(diags), "; ".join(str(d) for d in diags[:5]))


def _shared_executable(key, build, program, feed_specs, fetch_names,
                       scope_names, origin, why, extra, refresh=False):
    """The executable under ``key`` in the process-global registry, or
    ``build()``'s, published there: what both executors do when their own
    cache misses. ``refresh`` (use_program_cache=False) bypasses the lookup
    so THIS run re-traces, but still publishes the fresh compile — evicting
    instead would yank a live executable out from under unrelated
    executors / Predictor clones."""
    if not refresh:
        with _shared_lock:
            cp = _shared_executables.get(key)
            if cp is not None:
                _shared_executables.move_to_end(key)
        if cp is not None:
            exec_cache.record_trace_hit()
            return cp
    # compile OUTSIDE the registry lock: an XLA compile (plus any retry
    # backoff) must never stall other executors' unrelated cache misses.
    # Two threads racing the same key pay a duplicate compile and the
    # loser adopts the winner's entry below.
    exec_cache.record_trace_miss()
    exec_cache.configure()
    # FLAGS_verify_program: structural verification on the fresh-compile
    # path only (never per step) — a bad graph fails here with rule-tagged
    # diagnostics instead of an eval_shape traceback inside the build
    _maybe_verify(program, feed_specs, fetch_names, origin=origin)
    # one structured "why did this retrace" event per fresh compile,
    # diffed against the nearest cached key
    _explain.record_compile(dict(
        why, program=key[0], fetch_names=tuple(fetch_names),
        feed_specs=tuple(sorted(
            (n, (s, d)) for n, (s, d) in feed_specs.items()))),
        forced=refresh, program=program)

    def attempt():
        if _chaos.ENABLED:
            _chaos.fault("exec.compile")
        return build()

    # classified-transient failures on the fresh-compile path (flaky
    # cache reads, preempted backend compiles) retry under
    # FLAGS_dispatch_retries; verifier/user errors surface immediately
    cp = _retry.call(attempt, origin=origin.partition(".")[0] + ".compile")
    # stable cross-process key for the on-disk AOT image layer
    cp._exec_cache_key = executable_key(
        program, feed_specs, fetch_names, scope_names, extra=extra)
    with _shared_lock:
        winner = None if refresh else _shared_executables.get(key)
        if winner is not None:
            return winner
        _shared_executables[key] = cp
        while len(_shared_executables) > _SHARED_CAP:
            _shared_executables.popitem(last=False)
    return cp


def _on_device(arr, device):
    """Does this jax.Array live on ``device`` alone?"""
    return arr.sharding.device_set == {device}


def _state_value(name, val, cp, device):
    """A scope value as the step's executable takes it: on ``device``."""
    if not isinstance(val, jax.Array):
        return jax.device_put(np.asarray(val), device)
    if not _on_device(val, device):
        # Scope value lives on another Place's device (e.g. trained
        # on TPU, now serving on CPU): move it once.
        return jax.device_put(val, device)
    return val


_UNSET = object()


def _declared_feed_dtypes(program):
    """{feed name: declared numpy dtype or None} of ``program``, filled as
    names are fed and dropped when the program changes."""
    memo = getattr(program, "_feed_dtype_memo", None)
    if memo is None or memo[0] != program._version:
        memo = program._feed_dtype_memo = (program._version, {})
    return memo[1]


class _GatherPlan(object):
    """What one executable's state gather found in one scope: the holders
    of ``cp.state_in`` in order, per holder a weak reference to the value
    that last passed the device check (weak: a value replaced or erased
    from outside is not kept alive here), and after the first write-back
    the holders of the outputs."""

    __slots__ = ("membership", "holders", "seen", "outs")

    def __init__(self, cp, scope, membership):
        self.membership = membership
        self.holders = []
        for n in cp.state_in:
            # a name that has left the scope reads as an empty holder does
            self.holders.append((n, scope.find_var(n) or ScopeVariable(n)))
        self.seen = [None] * len(self.holders)
        self.outs = None


def _gather_state(cp, scope, place, device):
    """(plan, {name: value as the executable takes it}) for
    ``cp.state_in``. The holders are looked up once per (executable,
    scope) and kept in the scope while no name enters or leaves its chain;
    a value goes through ``place(name, value, cp, device)`` only if it is
    not the array that passed here, or was written back by
    ``_write_back``, last time."""
    membership = scope.membership()
    plan = scope._gather_plans.get(cp)
    rebuilt = plan is None or plan.membership != membership
    if rebuilt:
        plan = scope._gather_plans[cp] = _GatherPlan(cp, scope, membership)
    exec_cache.record_gather_plan(rebuilt)
    state = {}
    seen = plan.seen
    for i, (n, holder) in enumerate(plan.holders):
        val = holder.value
        ref = seen[i]
        if val is None or ref is None or ref() is not val:
            if val is None:
                raise RuntimeError(
                    "persistable variable %r is not initialized in the "
                    "scope (did you run the startup program?)" % n)
            moved = place(n, val, cp, device)
            # a value that had to be moved stays what it is in the
            # scope, and is moved again next run, as before
            seen[i] = weakref.ref(val) if moved is val else None
            val = moved
        state[n] = val
    return plan, state


def _write_back(plan, scope, new_state):
    """The step's outputs into the scope, through the holders."""
    outs = plan.outs
    if outs is None:
        # find-or-create HERE, as ``scope.set_value`` does, and only
        # now that the dispatch has succeeded. A holder this creates
        # changes the scope's membership: the next gather builds the
        # plan anew, and from then on nothing moves
        index = {n: i for i, (n, _h) in enumerate(plan.holders)}
        outs = plan.outs = [
            (n, scope.var(n), index.get(n, -1)) for n in new_state]
    seen = plan.seen
    holders = plan.holders
    for n, holder, i in outs:
        val = new_state[n]
        holder.value = val
        if i >= 0 and holders[i][1] is holder:
            seen[i] = weakref.ref(val)


# On-device finiteness scan for FLAGS_check_nan_inf: one fused executable
# of lax reductions per value-list structure; only the [n] bool vector
# crosses to the host, never the checked values.
_finite_stack = jax.jit(
    lambda vals: jnp.stack([jnp.all(jnp.isfinite(v)) for v in vals])
)


class FetchTimeoutError(RuntimeError):
    """``FetchHandle.result(timeout=...)`` expired before the fetches
    materialized. The handle itself is untouched: nothing was consumed,
    so a later ``result()`` (with or without a timeout) still returns
    the full values — the serving deadline path rejects the REQUEST,
    not the computation."""

    def __init__(self, timeout, fetch_names):
        super(FetchTimeoutError, self).__init__(
            "async fetch of %s did not materialize within %.3fs"
            % (list(fetch_names), timeout))
        self.timeout = timeout
        self.fetch_names = list(fetch_names)


class FetchHandle(object):
    """Live results of an async dispatch (``Executor.run_async``).

    The fetched values are in-flight device arrays; the handle never
    forces a host sync until asked:

      ``arrays()``             the live device arrays (non-blocking)
      ``done()``               True when every fetch has materialized
      ``block_until_ready()``  wait on device completion, no transfer
      ``result()``             numpy values (blocks; memoized) — matches
                               the equivalent ``run(...)`` bit-for-bit
      ``result(timeout=s)``    same, but raise :class:`FetchTimeoutError`
                               (leaving the handle reusable) if the
                               device work isn't done within ``s`` —
                               the deadline primitive the batching
                               server builds on, independent of the
                               watchdog
    """

    def __init__(self, arrays, fetch_names, nan_check=None, track=None,
                 t_dispatch=None, mem_device=None):
        self._arrays = list(arrays)
        self.fetch_names = list(fetch_names)
        self._nan_check = nan_check
        self._numpy = None
        # observability, all None on the undisturbed hot path: _track is
        # the profiler's async-span record, _t_dispatch the telemetry
        # dispatch timestamp, _mem_device the ledger label whose
        # 'activation' entries this handle releases at materialize
        # (all set only when their subsystem was ENABLED)
        self._track = track
        self._t_dispatch = t_dispatch
        self._mem_device = mem_device

    def __len__(self):
        return len(self._arrays)

    def arrays(self):
        return list(self._arrays)

    def done(self):
        for a in self._arrays:
            is_ready = getattr(a, "is_ready", None)
            if is_ready is not None and not is_ready():
                return False
        return True

    def block_until_ready(self):
        for a in self._arrays:
            if hasattr(a, "block_until_ready"):
                a.block_until_ready()
        return self

    def result(self, timeout=None):
        if self._numpy is None and timeout is not None:
            # Poll, don't block: jax arrays expose readiness but no timed
            # wait, and a blocking block_until_ready() here would make the
            # timeout a lie exactly when it matters (a wedged device).
            # Nothing is consumed before the readiness check, so a timed-
            # out handle can be asked again.
            deadline = time.monotonic() + float(timeout)
            pause = 5e-4
            while not self.done():
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise FetchTimeoutError(float(timeout),
                                            self.fetch_names)
                time.sleep(min(pause, remaining))
                pause = min(pause * 2, 0.05)
        if self._numpy is None:
            # a fetch that never materializes is the canonical silent
            # hang (wedged device, dead peer): the guard arms the
            # watchdog so a stall here is named in the black box
            with _blackbox.guard("FetchHandle.result"):
                if self._nan_check is not None:
                    # disarm only AFTER a clean pass: a caller that catches
                    # the NaN error and retries must get the error again,
                    # not the bad values
                    self._nan_check()
                    self._nan_check = None
                track = self._track
                if track is not None:
                    # split device-ready from host-transfer for the trace:
                    # block first (marks "ready"), then materialize
                    self.block_until_ready()
                    _profiler.async_fetch_ready(track)
                self._numpy = _materialize_fetches(
                    self._arrays, "FetchHandle.result")
                if track is not None:
                    _profiler.async_fetch_end(track)
                if self._mem_device is not None:
                    # the device copies of the fetches are released once
                    # numpy is in hand — balance the dispatch-time entries
                    _memory.drop_fetches(self.fetch_names,
                                         self._mem_device)
                    self._mem_device = None
                if self._t_dispatch is not None:
                    _telemetry.record_fetch_materialize(
                        time.perf_counter() - self._t_dispatch)
        return self._numpy


class Executor(object):
    def __init__(self, place=None):
        self.place = place if place is not None else TPUPlace()
        if not isinstance(self.place, Place):
            raise TypeError("place must be a Place (TPUPlace()/CPUPlace())")
        self._cache = {}
        self._run_counter = 0
        self._base_seed = np.random.randint(0, 2**31 - 1)

    # -- compilation cache --------------------------------------------------
    def _get_compiled(self, program, feed_specs, fetch_names, scope, device,
                      mode=_SINGLE, refresh=False):
        # Deferred shape inference must resolve BEFORE the fingerprint is
        # taken: filling shapes afterwards would change the content hash
        # and bust this very cache on the next run. No-op unless the
        # program still carries deferrals (reader pipelines).
        if getattr(program, "_deferred_infer", None):
            program.infer_deferred_shapes(
                feed_shapes={n: s for n, (s, _d) in feed_specs.items()})
        scope_names = scope.visible_names()
        key = (
            # content hash, not id(program): CPython reuses id() after GC,
            # and structurally identical programs should share the compile
            program_fingerprint(program),
            mode,
            tuple(sorted((n, s, d) for n, (s, d) in feed_specs.items())),
            tuple(fetch_names),
            # Scope contents shape the step signature (state_in): a var
            # initialized later (e.g. startup program ran) must recompile;
            # the NAME SET is the signature, so scopes holding the same
            # vars share executables (not id(scope)); the scope keeps the
            # frozenset, and with it its hash, while no name comes or goes
            scope_names,
            program._is_test,
            getattr(program, "_amp_dtype", None),
            # trace-time flags alter the lowered computation; toggling one
            # must recompile, not reuse the stale executable
            trace_flags_key(),
            (device.platform, device.id),
        )
        cp = None if refresh else self._cache.get(key)
        if cp is not None:
            exec_cache.record_trace_hit()
            return cp
        multi = mode is not _SINGLE

        def build():
            if multi:
                return MultiStepProgram(
                    program, mode[1], feed_specs, fetch_names, scope_names,
                    is_test=program._is_test, device=device,
                    stack_fetches=mode[2])
            return CompiledProgram(
                program, feed_specs, fetch_names, scope_names,
                is_test=program._is_test, device=device)

        cp = self._cache[key] = _shared_executable(
            key, build, program, feed_specs, fetch_names, scope_names,
            origin=(_RUN_MULTI if multi else _RUN).name,
            why={"scope_signature": scope_names, "flags": key[7],
                 "device": "%s:%d" % (device.platform, device.id),
                 "mode": "multi_step[%d]" % mode[1] if multi else "single"},
            # device.id included so executors pinned to different local
            # devices never share one baked image
            extra=mode + (device.platform, device.id,
                          getattr(device, "device_kind", "")),
            refresh=refresh)
        return cp

    def compiled_text(self, program):
        """Optimized-HLO text of every executable this executor has run
        for ``program`` (one per feed-shape/fetch-list combination;
        multi-step scans included) — see
        ``CompiledProgram.compiled_text``."""
        fp = program_fingerprint(program)
        return [cp.compiled_text() for key, cp in self._cache.items()
                if key[0] == fp]

    def _enter(self, entry, program, feed, fetch_list, scope,
               return_numpy=True, mode=_SINGLE, refresh=False,
               in_flight=None):
        device = self.place.jax_device()
        # Everything in the step (feed transfer, key creation, dispatch)
        # stays on the Place's device: with several backends loaded (TPU
        # plugin + CPU), stray ops like PRNGKey would otherwise run on the
        # default platform — wrong device, and unsafe under concurrent
        # serving.
        with jax.default_device(device):
            return _run_step(
                self, entry, program or framework.default_main_program(),
                feed or {}, fetch_list or [], scope or global_scope(),
                device, return_numpy, mode, refresh, in_flight)

    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
    ):
        # reference use_program_cache=False semantics: this run re-traces;
        # both of this executor's caches are bypassed (not purged) via
        # refresh — see _shared_executable
        return self._enter(_RUN, program, feed, fetch_list, scope,
                           return_numpy, refresh=not use_program_cache)

    # -- shared run plumbing -------------------------------------------------
    def _prepare_feeds(self, program, feed, device):
        """numpy/LoDTensor feeds -> (arrays, (shape, dtype) specs), cast to
        the declared var dtype when compatible. Host values stay numpy:
        they go to the device as arguments of the step's executable, whose
        ``in_shardings`` pin it, and not one ``jax.device_put`` each."""
        feeds = {}
        feed_specs = {}
        declared = _declared_feed_dtypes(program)
        for name, value in feed.items():
            arr = _as_feed_array(value)
            want = declared.get(name, _UNSET)
            if want is _UNSET:
                var = program.global_block()._find_var_recursive(name)
                want = declared[name] = (
                    np_dtype(var.dtype)
                    if var is not None and var.dtype else None)
            if want is not None and arr.dtype != want:
                if np.issubdtype(arr.dtype, np.floating) or np.issubdtype(
                    arr.dtype, np.integer
                ):
                    arr = arr.astype(want)
            if isinstance(arr, jax.Array) and not _on_device(arr, device):
                arr = jax.device_put(arr, device)
            feeds[name] = arr
            feed_specs[name] = (tuple(arr.shape), str(arr.dtype))
        return feeds, feed_specs

    def _step_key(self, program, device):
        """The run's key as the step's executable takes it: the base key
        of the seed, kept on the device, and the run counter as a host
        integer; ``lowering.fold_step_key`` folds them inside."""
        self._run_counter += 1
        return step_key(program.random_seed or self._base_seed,
                        self._run_counter, device)

    @staticmethod
    def _dispatch(cp, state, feeds, key, origin="Executor.dispatch"):
        """The XLA dispatch, under the resilience shell: the chaos
        ``exec.dispatch`` kill-point fires first (so injected faults are
        indistinguishable from real transient ones), and with
        ``FLAGS_dispatch_retries`` set, classified-transient failures
        back off and retry — vetoed the moment a failed attempt has
        already consumed the donated state buffers (retrying would crash
        on deleted arrays and mask the real error). Both subsystems off:
        two module-bool/flag reads around the plain call. A
        RESOURCE_EXHAUSTED/OOM escaping any path — deterministic, so
        never retried — is upgraded to the M001 diagnostic (black-box
        dump with the ledger's top holders + the predicted peak) on the
        way out; one substring check, paid only on the failure path."""
        chaos_on = _chaos.ENABLED
        if _lock_witness.ENABLED:
            # a witnessed lock held right now spans this device dispatch
            _lock_witness.note_dispatch()
        try:
            if not _retry.retries_enabled():
                if chaos_on:
                    _chaos.fault("exec.dispatch")
                return cp(state, feeds, key)

            def _run():
                if chaos_on:
                    _chaos.fault("exec.dispatch")
                return cp(state, feeds, key)

            return _retry.call(_run, origin=origin, donated=state)
        except Exception as exc:
            if _memory.is_oom(exc) and not isinstance(
                    exc, _memory.MemoryExhaustedError):
                _memory.enrich_and_raise(exc, origin=origin)
            raise

    @staticmethod
    def _nan_check_start(new_state, fetch_names, fetches):
        """FLAGS_check_nan_inf (operator.cc:754) in two phases: the scan
        is an on-device lax reduction fused into one tiny executable,
        DISPATCHED NOW — while the checked arrays are still live; a later
        step may donate these very buffers — and only an [n] bool vector
        crosses to the host when the returned ``finish`` callable runs
        (the old implementation np.asarray'd EVERY output, a full host
        transfer + sync per checked run). Returns None when the flag is
        off."""
        from paddle_tpu import flags as _flags

        if not _flags.get("check_nan_inf"):
            return None
        names, vals, host_bad = [], [], None
        for name, val in list(new_state.items()) + list(
            zip(fetch_names, fetches)
        ):
            if isinstance(val, jax.Array) and jnp.issubdtype(
                val.dtype, jnp.floating
            ):
                names.append(name)
                vals.append(val)
                continue
            arr = np.asarray(val)  # host-side values (rare): check directly
            if host_bad is None and np.issubdtype(
                arr.dtype, np.floating
            ) and not np.all(np.isfinite(arr)):
                host_bad = name
        flags_dev = _finite_stack(vals) if vals else None

        def finish():
            bad = host_bad
            if bad is None and flags_dev is not None:
                finite = np.asarray(flags_dev)
                if not finite.all():
                    bad = names[int(np.argmin(finite))]
            if bad is not None:
                raise RuntimeError(
                    "NaN/Inf detected in variable %r after program run "
                    "(FLAGS_check_nan_inf)" % bad
                )

        return finish

    @staticmethod
    def _nan_snapshot(cp, state):
        """Pre-step snapshot for the NaN-provenance replay: the step is
        pure, so (state, feeds, key) reproduce it exactly — but dispatch
        DONATES the mutable state buffers, so those are copied on device
        first (frozen state and feeds survive by reference). None unless
        both FLAGS_check_nan_inf and FLAGS_nan_provenance are on."""
        from paddle_tpu import flags as _flags

        if not (_flags.get("check_nan_inf")
                and _flags.get("nan_provenance")):
            return None
        snap = {n: state[n] for n in cp.frozen_state}
        for n in cp.mutable_state:
            v = state[n]
            snap[n] = jnp.array(v, copy=True) if isinstance(
                v, jax.Array) else v
        return snap

    @staticmethod
    def _nan_blame(exc, program, snapshot, feeds, key, device, steps,
                   mutable_state, multi):
        """The scanner tripped: replay from the snapshot and raise the
        enriched NonFiniteError naming the first bad op. ``multi`` routes
        through the scan-body replay (per-step fold_in keys) even for
        steps == 1."""
        from paddle_tpu.observability import nan_provenance as _nanprov

        # the replay is eager: it wants the run's key as a value and the
        # feeds as arrays, which the dispatch itself never made
        key = step_key_value(key)
        feeds = {n: jnp.asarray(a) for n, a in feeds.items()}
        _nanprov.enrich_and_raise(
            exc, program, snapshot, feeds, key, steps=steps,
            mutable_state=mutable_state, is_test=program._is_test,
            platform=getattr(device, "platform", None), multi=multi)

    # -- what ``_run_step`` asks of its executor beyond the methods above;
    # ParallelExecutor answers each for a mesh ------------------------------
    _place_state = staticmethod(_state_value)
    _fetch_to_numpy = staticmethod(np.asarray)
    # the step's outputs replace the donated inputs under one device label
    _book_state = staticmethod(_memory.track_state)

    @staticmethod
    def _book_plan(cp, program, feeds, feed_specs, fingerprint, device):
        """The ledger label feeds, state and fetches book under; the
        predicted plan is filed once per executable so the step records
        and any OOM dump carry predicted-vs-measured peak."""
        _memory.register_plan_for(cp, program, feed_specs, fingerprint)
        return _telemetry.device_label(device)

    @staticmethod
    def _device_times(fetches, new_state, t_dispatch):
        """Per-device dispatch->ready latencies: a mesh's signal."""
        return None

    @staticmethod
    def _dispatch_fields(mode):
        """What the black box's dispatch event carries beyond the specs."""
        return {"steps": mode[1]} if mode is not _SINGLE else {}

    def run_async(self, program=None, feed=None, fetch_list=None,
                  feed_var_name="feed", fetch_var_name="fetch", scope=None):
        """``run`` without the host sync: dispatches one step and returns
        a :class:`FetchHandle` of live device arrays immediately — the
        XLA execution proceeds asynchronously and ``.result()``
        materializes numpy lazily, matching ``run(...)`` bit-for-bit.
        Scope state is updated with live (also non-blocking) arrays, so
        back-to-back dispatches chain on device without host round trips.
        """
        return self._enter(_RUN_ASYNC, program, feed, fetch_list, scope,
                           return_numpy=False)

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, return_numpy=True, stack_fetches=False,
                       in_flight=None):
        """Run ``steps`` iterations of ``program`` inside ONE compiled
        executable (lax.scan over the step function) — one host dispatch
        per K steps instead of per step. ``feed`` is constant across the
        steps (real pipelines use in-graph reader ops and need none).
        Fetches are the LAST step's values; pass stack_fetches=True for
        the per-step trajectory stacked along a leading [steps] axis
        (costs scan output buffers every iteration). ``in_flight`` is
        called once, with no argument, after the dispatch is launched and
        before anything waits for it (with ``return_numpy=False``: before
        the return): host work the caller has that can run beside the
        device. It must not raise, and its seconds are in the dispatch
        record's wall and in none of its phases."""
        return self._enter(_RUN_MULTI, program, feed, fetch_list, scope,
                           return_numpy,
                           mode=("multi", int(steps), bool(stack_fetches)),
                           in_flight=in_flight)

    def close(self):
        self._cache.clear()

    # -- parity helpers -----------------------------------------------------
    def _run_startup(self, startup_program=None, scope=None):
        self.run(
            startup_program or framework.default_startup_program(),
            feed={},
            fetch_list=[],
            scope=scope,
        )


def _with_blame(check, *replay):
    """``check`` with its error upgraded by the NaN-provenance replay."""
    def checked():
        try:
            check()
        except RuntimeError as e:
            Executor._nan_blame(e, *replay)

    return checked


def _run_step(ex, entry, program, feed, fetch_list, scope, device,
              return_numpy=True, mode=_SINGLE, refresh=False, in_flight=None):
    """One step of ``program``, from feeds to fetches: the one path under
    ``Executor.run``, ``run_async``, ``run_multi_step`` and
    ``ParallelExecutor.run``, and the one place each observer is hooked.
    ``entry`` names the caller to them; ``ex`` prepares the feeds, resolves
    the executable, places state, makes the key and books the ledger, for
    one ``device`` or (``device`` None) for its mesh."""
    multi = mode is not _SINGLE
    steps = mode[1] if multi else 1
    as_handle = entry is _RUN_ASYNC
    # forensics shell: the watchdog sees one armed unit of blocking work
    # (scale: one dispatch of K steps legitimately blocks ~K× the per-step
    # p95 its auto timeout is derived from); any escaping exception lands
    # in the black box before it propagates
    with _blackbox.guard(entry.name, scale=steps), _stepprof.DROP_ON_ERROR:
        # flight-recorder guards: one module-bool load each. The phase
        # brackets (sp) are always on: every dispatch leaves its small
        # record (step_profiler.dispatch_records); what is heavy there
        # waits for FLAGS_step_profile
        telem = _telemetry.ENABLED
        prof = _profiler.enabled()
        sp = _stepprof.begin(entry.origin)
        t0 = sp.t0   # telemetry and the profiler read the span's stamp
        sp.enter("feed")
        feeds, feed_specs = ex._prepare_feeds(program, feed, device)
        t_feed = time.perf_counter() if telem else 0.0
        fetch_names = _fetch_names(fetch_list)
        # a cache hit closes this bracket in microseconds; a fresh
        # XLA trace shows up as a fat compile phase instead of
        # silently inflating the step
        sp.enter("compile")
        cp = ex._get_compiled(program, feed_specs, fetch_names, scope,
                              device, mode, refresh)
        # state gather + step-key derivation assemble the dispatch
        # inputs just like the feed dict does — same bracket, or
        # they'd surface as unattributed host time
        sp.enter("feed")
        plan, state = _gather_state(cp, scope, ex._place_state, device)
        key = ex._step_key(program, device)
        # the bracket opens here, not at _dispatch: pre-dispatch
        # work — the profiler's own one-shot cost snapshot, the
        # blackbox record, the nan snapshot — is host dispatch
        # overhead and must be charged, not hidden in the
        # unattributed residual
        sp.enter("dispatch")
        if _stepprof.ENABLED:
            sp.pre_dispatch(cp, state, feeds, key, program)
        fingerprint = flops_avals = mem_dev = device_times = None
        if telem:
            # per-EXECUTABLE key: two feed shapes of one program do
            # different FLOPs, so the program fingerprint alone would
            # mis-price steps
            fingerprint = _telemetry.executable_fingerprint(cp, program)
            flops_avals = _telemetry.capture_step_avals(
                cp, state, feeds, key)
            # HBM ledger: feeds enter the device here
            mem_dev = ex._book_plan(cp, program, feeds, feed_specs,
                                    fingerprint, device)
            _memory.track_feeds(feeds, mem_dev)
        if _blackbox.ENABLED:
            # the event a crash dump's last entry points at: what was
            # about to run, with the shapes that ran it
            _blackbox.record_dispatch(
                entry.name, feed_specs=feed_specs, fetch_names=fetch_names,
                fingerprint=getattr(cp, "_exec_cache_key", None),
                **ex._dispatch_fields(mode))
        nan_snapshot = Executor._nan_snapshot(cp, state)
        t_dispatch = time.perf_counter() if telem else 0.0
        new_state, fetches = Executor._dispatch(cp, state, feeds, key,
                                                origin=entry.dispatch)
        # scope writeback is output handling on the host clock —
        # fetch-side work, even when the caller fetched nothing
        sp.enter("fetch")
        _write_back(plan, scope, new_state)
        # the donated inputs are dead once the scope holds the outputs:
        # released here, inside the bracket, and not at this frame's
        # teardown, where no record would see it (hundreds of arrays:
        # a tenth of a small dispatch)
        del state
        if telem:
            # scope binding: the step's outputs replace the donated
            # inputs under the same ledger keys; feeds leave with the
            # host references, fetched activations stay live until
            # materialized (below / FetchHandle.result)
            ex._book_state(cp, program, new_state, mem_dev)
            _memory.track_fetches(cp.fetch_names, fetches, mem_dev)
            _memory.drop_feeds(feeds, mem_dev)
        # the fetch bracket closes AFTER the ledger writeback: when
        # telemetry is co-enabled its per-step accounting is still
        # output handling on the host clock, not unattributed
        # residual
        sp.exit()
        # the (optional) nan/inf reductions are in flight on device from
        # here; reading their verdict is the sync return's, or waits for
        # FetchHandle.result
        nan_check = Executor._nan_check_start(
            new_state, cp.fetch_names, fetches)
        if nan_check is not None and nan_snapshot is not None:
            nan_check = _with_blame(
                nan_check, program, nan_snapshot, feeds, key, device,
                steps, cp.mutable_state, multi)
        if in_flight is not None:
            # the dispatch (and the optional scan) is launched and nothing
            # has waited for it: the caller's own host work runs here,
            # beside the device, between two brackets
            sp.outside(in_flight)
        if as_handle:
            # dispatch complete, nothing synced. The span measured host
            # dispatch latency only; device + fetch happen in
            # FetchHandle.result on the caller's clock, so the record
            # (and telemetry's: kept out of percentiles/MFU) is marked
            # dispatch_only
            result = FetchHandle(
                fetches, cp.fetch_names,
                nan_check=nan_check,
                track=_profiler.async_fetch_begin(cp.fetch_names)
                if prof else None,
                t_dispatch=t0 if telem else None,
                mem_device=mem_dev,
            )
            fetches = ()
        else:
            if nan_check is not None:
                nan_check()
            if return_numpy:
                # device bracket: wait for compute to complete BEFORE
                # the host copy, so device time and d2h materialize are
                # attributed separately (and annotated on the profiler's
                # clock for whoever traces)
                sp.enter("device")
                if telem:
                    # measured on the live arrays BEFORE any host
                    # materialization, and only on the path that syncs
                    # anyway: it blocks on device shards, so it IS device
                    # wait, and the per-fetch block_until_ready below
                    # returns instantly having been paid here
                    device_times = ex._device_times(
                        fetches, new_state, t_dispatch)
                with _stepprof.device_annotation():
                    for _f in fetches:
                        if hasattr(_f, "block_until_ready"):
                            _f.block_until_ready()
                sp.enter("fetch")
                fetches = _materialize_fetches(fetches, entry.name,
                                               ex._fetch_to_numpy)
                sp.exit()
            result = fetches
        # the span closes BEFORE telemetry's own record-keeping
        # tail: the observatory reports the same step wall whether
        # or not other observers are armed, and their bookkeeping
        # cannot masquerade as unattributed step residual
        _stepprof.finish(sp, steps=steps, feeds=feeds, fetches=fetches,
                         dispatch_only=as_handle)
        if telem and not as_handle:
            # sync return: the fetch buffers are the caller's now (numpy
            # in hand, or live arrays the executor no longer owns)
            _memory.drop_fetches(cp.fetch_names, mem_dev)
        if telem or prof:
            t1 = time.perf_counter()
            if telem:
                _telemetry.record_step(
                    entry.origin, t1 - t0, steps=steps,
                    feed_bytes=sum(
                        getattr(a, "nbytes", 0) for a in feeds.values()),
                    fetch_bytes=sum(
                        getattr(f, "nbytes", 0) for f in fetches),
                    h2d_seconds=t_feed - t0, fingerprint=fingerprint,
                    dispatch_only=as_handle, device_times=device_times)
                if flops_avals is not None:
                    _telemetry.register_flops_from_avals(
                        cp, fingerprint, flops_avals, steps=steps)
            if prof:
                _profiler.record_span(
                    entry.span % steps if multi else entry.span, t0, t1)
        return result
