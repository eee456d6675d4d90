"""Persistent cross-process executable cache + compile-tax telemetry.

Every process start (serving replica, bench run, CI shard) used to pay the
full XLA compile from scratch. This module kills that tax in two layers:

1. **XLA compile cache**: JAX's persistent compilation cache. A warm
   process still re-traces the program to HLO, but the backend compile is
   replaced by a disk load (content hash of the HLO module, so it also
   dedups across Executor instances and structurally identical
   programs). It is PLACED FROM OUTSIDE: where the environment sets
   ``JAX_COMPILATION_CACHE_DIR`` the cache stays there and this module
   sets no other directory; otherwise it goes to one fixed path inside
   the checkout (``xla_cache_dir()``). The directory is part of JAX's
   cache key, so it never derives from a temp name, a pid or a time —
   a path that moves never hits. Nothing here ever switches it off.
   ``enable_xla_cache()`` turns it on (``chip_smoke.py`` and
   ``perfbench/`` do at start; ``FLAGS_exec_cache_dir`` implies it).
2. **AOT executable images** (``<FLAGS_exec_cache_dir>/aot``; empty flag
   = layer off, zero overhead): serialized ``lower()``/``compile()``
   output of the whole step function, keyed by
   ``fingerprint.executable_key`` x argument avals x jax/jaxlib versions.
   A warm process skips even the trace: the executable deserializes
   straight into a callable.

Corruption/eviction tolerance: every load path catches, counts,
*quarantines* the bad entry (``<aot>/quarantine/`` — moved aside for
autopsy, never re-read) and falls back to a fresh compile — a bad cache
entry can cost time, never correctness, and never a crash.
``FLAGS_exec_cache_max_bytes`` bounds both layers (LRU on the XLA cache,
oldest-mtime trim on AOT files).

TRUST BOUNDARY: AOT images deserialize through pickle, so the cache dir
must be writable only by principals you would let execute code in this
process (dirs are created 0o700; never point the flag at a
world-writable path).

Stats: counters below are exported through ``profiler.exec_cache_stats()``
(``compile_seconds_cold``/``compile_seconds_warm`` among them) and read by
``perfbench/``. Backend compile time is observed via ``jax.monitoring`` events, so
compiles that happen outside this module (stray helper jits) are counted
too — the numbers are the process's whole compile tax, not just the
executor's share. ``trace_seconds`` / ``lower_seconds`` come from the same
taps: the time JAX spent tracing Python to jaxprs and lowering jaxprs to
MLIR modules, which no cache layer here saves. ``by_function`` is the same
three totals by the jitted function's name (docs/OBSERVABILITY.md, "The
set-up ledger"): an executable's own step, the IR builder's shape
inference, a kernel's body, a stray helper each have a row.
"""

import hashlib
import os
import pickle
import tempfile
import threading

from paddle_tpu.observability import lock_witness
import time

import jax
from jax.experimental.compilation_cache import compilation_cache

_lock = lock_witness.make_lock("core.exec_cache")
_tls = threading.local()

_STAT_KEYS = (
    "trace_cache_hits",      # in-process CompiledProgram reuse (executor)
    "trace_cache_misses",    # CompiledProgram constructions (re-traces)
    "backend_compiles",      # XLA backend compile calls observed
    "persistent_hits",       # backend compiles served from the disk cache
    "persistent_misses",     # backend compiles that ran for real
    "aot_hits",              # whole executables deserialized from disk
    "aot_misses",
    "aot_errors",            # corrupt/incompatible AOT entries tolerated
    # the executor's state gather (executor._GatherPlan): runs that found
    # their (executable, scope) holders kept from last time, and runs
    # that had to look them up (first run, or a name came or went)
    "gather_plan_hits",
    "gather_plan_rebuilds",
)

_stats = {k: 0 for k in _STAT_KEYS}
_stats.update(
    compile_seconds=0.0,         # total wall time inside backend compiles
    compile_seconds_cold=0.0,    # ...attributable to fresh compiles
    compile_seconds_warm=0.0,    # ...attributable to cache loads
    cache_retrieval_seconds=0.0,
    # what precedes every backend compile, cache hit or not, and what
    # the persistent cache cannot save: tracing the Python program to a
    # jaxpr and lowering the jaxpr to an MLIR module
    trace_seconds=0.0,
    lower_seconds=0.0,
    # the part of ``lower_seconds`` that ``trace_seconds`` counts too:
    # tracing reports that fired inside a lowering (a lowering rule that
    # traces; 0.05-0.17 s of a cell's set-up on the chip, PERF.md, PR 51)
    trace_in_lower_seconds=0.0,
)
# {fun_name: [calls, trace_s, lower_s, backend_s]}: ``trace_seconds``,
# ``lower_seconds`` and ``compile_seconds`` by jitted function; past the
# cap, under "<other>"
_by_function = {}
_FUNCTIONS_CAP = 256
_TRACE, _LOWER, _BACKEND = 1, 2, 3

_configured = {"dir": None}


# -- monitoring taps ---------------------------------------------------------
def _on_event(name, **kw):
    if name == "/jax/compilation_cache/compile_requests_use_cache":
        # fires at the start of every cache-consulting compile: clearing
        # here keeps a stale hit/miss verdict from a compile that never
        # emitted its duration event out of the next attribution
        _tls.last = None
    elif name == "/jax/compilation_cache/cache_hits":
        with _lock:
            _stats["persistent_hits"] += 1
        _tls.last = "hit"
    elif name == "/jax/compilation_cache/cache_misses":
        with _lock:
            _stats["persistent_misses"] += 1
        _tls.last = "miss"


def _book(kind, fun_name, secs):
    """``secs`` to ``fun_name``'s row of ``by_function``. Under _lock."""
    # a module is named jit_<function>, an eager primitive's
    # jit(<primitive>): one name for the three kinds
    fun_name = str(fun_name or "?")
    if fun_name.startswith("jit_"):
        fun_name = fun_name[4:]
    elif fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    if fun_name not in _by_function and len(_by_function) >= _FUNCTIONS_CAP:
        fun_name = "<other>"
    row = _by_function.setdefault(fun_name, [0, 0.0, 0.0, 0.0])
    row[0] += kind == _TRACE
    row[kind] += secs


def _on_duration(name, secs, fun_name=None, **kw):
    if name == "/jax/core/compile/backend_compile_duration":
        # the hit/miss event for THIS compile fired earlier on this same
        # thread (jax records them synchronously inside the compile call),
        # so a thread-local carries the attribution across the two taps
        last = getattr(_tls, "last", None)
        _tls.last = None
        with _lock:
            _stats["backend_compiles"] += 1
            _stats["compile_seconds"] += secs
            if last == "hit":
                _stats["compile_seconds_warm"] += secs
            else:
                _stats["compile_seconds_cold"] += secs
            _book(_BACKEND, fun_name, secs)
        _record_compile_span("xla_backend_compile", secs,
                             "warm" if last == "hit" else "cold")
    elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
        with _lock:
            _stats["cache_retrieval_seconds"] += secs
    elif name == "/jax/core/compile/jaxpr_trace_duration":
        # a jitted function called inside a trace reports its own trace
        # first and lies inside the outer one's: count the union. The
        # event fires at the trace's END on the tracing thread, so the
        # reports this one encloses are the last ones of this thread
        # that ended after it began
        end = time.perf_counter()
        done = getattr(_tls, "traces", None)
        if done is None:
            done = _tls.traces = []
        net = secs
        while done and done[-1][0] > end - secs:
            net -= done.pop()[1]
        done.append((end, secs))
        if len(done) > 1024:
            del done[:512]
        with _lock:
            _stats["trace_seconds"] += net
            _book(_TRACE, fun_name, net)
    elif name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        # one module a top-level jit, so no lowering lies inside another
        # and the sum needs no union; what does lie inside one is tracing,
        # which ``trace_seconds`` has counted: those seconds are in both
        # totals, and ``trace_in_lower_seconds`` says how many they are
        began = time.perf_counter() - secs
        inside = 0.0
        for end, traced in reversed(getattr(_tls, "traces", ())):
            if end <= began:
                break
            inside += traced
        with _lock:
            _stats["lower_seconds"] += secs
            _stats["trace_in_lower_seconds"] += inside
            _book(_LOWER, fun_name, secs)


def _record_compile_span(name, secs, kind):
    """Land the compile in the profiler's unified trace stream (cat
    ``compile``). The duration event fires at compile END, so the span is
    back-dated by its length; no-op when the profiler is off."""
    try:
        from paddle_tpu import profiler

        if profiler.enabled():
            end = time.perf_counter()
            profiler.record_span(name, end - secs, end, cat="compile",
                                 args={"kind": kind})
    except Exception:
        pass


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def record_trace_hit():
    with _lock:
        _stats["trace_cache_hits"] += 1


def record_trace_miss():
    with _lock:
        _stats["trace_cache_misses"] += 1


def record_gather_plan(rebuilt):
    with _lock:
        _stats["gather_plan_rebuilds" if rebuilt
               else "gather_plan_hits"] += 1


def stats():
    """Snapshot of the cache counters. ``fresh_compiles`` is the number of
    XLA compiles no cache layer could serve — tests/test_exec_cache.py
    asserts it is zero in a second process sharing the cache.
    ``enabled``/``cache_dir`` describe the AOT image layer (the flag);
    ``xla_cache_dir`` is where JAX's persistent cache is live, or None.
    ``by_function`` is ``{fun_name: [calls, trace_s, lower_s, backend_s]}``:
    the columns add up to ``trace_seconds``, ``lower_seconds`` and
    ``compile_seconds``."""
    with _lock:
        snap = dict(_stats)
        # fresh rows a call: a caller's shallow copy of the snapshot (the
        # benchmark's, at the end of warm-up) keeps the table as it was
        snap["by_function"] = {k: list(v) for k, v in _by_function.items()}
    snap["enabled"] = _configured["dir"] is not None
    snap["cache_dir"] = _configured["dir"]
    snap["xla_cache_dir"] = (
        jax.config.jax_compilation_cache_dir
        if jax.config.jax_enable_compilation_cache else None)
    snap["fresh_compiles"] = (
        snap["persistent_misses"] if snap["xla_cache_dir"]
        else snap["backend_compiles"]
    )
    return snap


def reset_stats():
    with _lock:
        for k in _stats:
            _stats[k] = 0.0 if isinstance(_stats[k], float) else 0
        _by_function.clear()


# -- configuration -----------------------------------------------------------
# the checkout root (this file is paddle_tpu/core/exec_cache.py); listed
# in .gitignore
_DEFAULT_XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def xla_cache_dir():
    """Where JAX's persistent compile cache belongs: where the
    environment put it, else the one fixed path inside the checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or _DEFAULT_XLA_CACHE_DIR)


def enable_xla_cache():
    """Turn JAX's persistent compile cache on at ``xla_cache_dir()``.
    Idempotent. With ``JAX_COMPILATION_CACHE_DIR`` set JAX already
    points there and no directory is set here."""
    target = xla_cache_dir()
    if jax.config.jax_compilation_cache_dir != target:
        os.makedirs(target, mode=0o700, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
        # jax binds its file cache at the first compile; a dir set
        # after that only lands once the handle is dropped
        compilation_cache.reset_cache()
    # the defaults skip "too fast / too small" entries; this cache
    # exists to make every process start warm, so persist everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # a corrupt entry must degrade to a fresh compile, never a crash
    jax.config.update("jax_raise_persistent_cache_errors", False)
    _apply_max_bytes()
    return target


def configure(cache_dir=None):
    """Point the AOT image layer at ``cache_dir`` (default: the
    ``exec_cache_dir`` flag) and, when that is non-empty, make sure
    JAX's compile cache is on (``enable_xla_cache``). Idempotent; safe
    to call per compile. An empty dir turns the AOT layer off and
    leaves JAX's cache exactly as the environment or an earlier
    ``enable_xla_cache()`` set it."""
    if cache_dir is None:
        from paddle_tpu import flags

        cache_dir = flags.get("exec_cache_dir")
    cache_dir = os.path.abspath(cache_dir) if cache_dir else None
    if cache_dir == _configured["dir"]:
        if cache_dir is not None:
            _apply_max_bytes()  # a flag change must land without a dir change
        return cache_dir
    if cache_dir is not None:
        # 0o700: AOT images load via pickle, so the dir is code-execution
        # trusted — keep it private to this user (see module docstring)
        os.makedirs(cache_dir, mode=0o700, exist_ok=True)
        os.makedirs(os.path.join(cache_dir, "aot"), mode=0o700,
                    exist_ok=True)
        enable_xla_cache()
    _configured["dir"] = cache_dir
    return cache_dir


def _apply_max_bytes():
    """The flag is the TOTAL budget: half to the XLA layer (jax's LRU),
    half to the AOT image layer (_trim_aot_dir). Always written —
    including back to -1/unbounded — so a stale cap from an earlier
    configuration can't linger."""
    max_bytes = _max_bytes()
    jax.config.update(
        "jax_compilation_cache_max_size",
        max_bytes // 2 if max_bytes > 0 else -1,
    )


def _max_bytes():
    from paddle_tpu import flags

    try:
        return int(flags.get("exec_cache_max_bytes"))
    except (KeyError, TypeError, ValueError):
        return -1


def enabled():
    return _configured["dir"] is not None


# -- AOT executable images ---------------------------------------------------
def _version_tag():
    import jaxlib

    return "%s|%s" % (jax.__version__, getattr(jaxlib, "__version__", "?"))


def _args_signature(args):
    """Digest of the argument pytree structure + leaf avals: the compiled
    executable is only valid for exactly these shapes/dtypes."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    for leaf in leaves:
        parts.append(
            "%s%s" % (getattr(leaf, "dtype", type(leaf).__name__),
                      tuple(getattr(leaf, "shape", ())))
        )
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def _aot_path(disk_key, args):
    full = hashlib.sha256(
        ("%s|%s|%s" % (disk_key, _args_signature(args), _version_tag()))
        .encode()
    ).hexdigest()
    return os.path.join(_configured["dir"], "aot", full + ".exe")


def _remove_quiet(path):
    try:
        os.remove(path)
    except OSError:
        pass


def _quarantine_aot(path):
    """A corrupt AOT image is moved into ``<aot>/quarantine/``, not
    deleted: execution already degraded safely to a fresh compile, and
    quarantining both preserves the bytes for autopsy (was it a torn
    write? a bad disk? an incompatible producer?) and guarantees the
    same poisoned entry can never be re-read — deletion invites the
    writer that produced it to reproduce it. Falls back to deletion when
    the rename itself fails (e.g. a full disk)."""
    qdir = os.path.join(os.path.dirname(path), "quarantine")
    try:
        os.makedirs(qdir, mode=0o700, exist_ok=True)
        os.replace(path, os.path.join(qdir, os.path.basename(path)))
        # bounded evidence locker: a host with a flaky disk quarantines
        # on every bad read — keep the newest few, or recurring
        # corruption grows outside the FLAGS_exec_cache_max_bytes budget
        entries = sorted(
            (os.stat(p).st_mtime, p)
            for p in (os.path.join(qdir, n) for n in os.listdir(qdir))
            if os.path.isfile(p))
        for _, p in entries[:-8]:
            _remove_quiet(p)
    except OSError:
        _remove_quiet(path)
        return None
    try:
        from paddle_tpu.observability import blackbox

        if blackbox.ENABLED:
            blackbox.record("exec_cache_quarantine",
                            entry=os.path.basename(path))
    except Exception:
        pass
    return qdir


def _load_aot(path):
    if not os.path.exists(path):
        return None
    t0 = time.perf_counter()
    try:
        from paddle_tpu.resilience import chaos as _chaos

        if _chaos.ENABLED:
            _chaos.fault("aot.read")
        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        from jax.experimental import serialize_executable

        loaded = serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree
        )
    except Exception:
        # corrupt, truncated, or built by an incompatible runtime that
        # slipped past the version tag: tolerate, quarantine, recompile
        with _lock:
            _stats["aot_errors"] += 1
        _quarantine_aot(path)
        return None
    dt = time.perf_counter() - t0
    with _lock:
        _stats["aot_hits"] += 1
        _stats["compile_seconds"] += dt
        _stats["compile_seconds_warm"] += dt
        _book(_BACKEND, "aot_image_load", dt)
    _record_compile_span("aot_image_load", dt, "warm")
    try:
        # HBM ledger (observability/memory.py): a deserialized image's
        # program+constants occupy device memory for the process's life —
        # the 'cache' kind on the live-bytes gauge. Serialized size is
        # the accountable proxy; the true on-device footprint is XLA's.
        from paddle_tpu.observability import memory as _memory

        if _memory.ENABLED:
            _memory.track("aot:" + os.path.basename(path),
                          os.path.getsize(path), "cache")
    except Exception:
        pass
    return loaded


def _store_aot(path, compiled):
    try:
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps(
            (payload, in_tree, out_tree), protocol=pickle.HIGHEST_PROTOCOL
        )
        d = os.path.dirname(path)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)  # atomic: readers see old or new, never torn
        except BaseException:
            _remove_quiet(tmp)
            raise
        _trim_aot_dir(d)
    except Exception:
        with _lock:
            _stats["aot_errors"] += 1


def _trim_aot_dir(d):
    """Oldest-mtime eviction once the AOT layer exceeds its half of the
    total byte budget (the XLA layer holds the other half)."""
    budget = _max_bytes() // 2
    if budget <= 0:
        return
    try:
        entries = []
        for name in os.listdir(d):
            p = os.path.join(d, name)
            if not os.path.isfile(p):
                continue  # the quarantine subdir is not budget-evictable
            st = os.stat(p)
            entries.append((st.st_mtime, st.st_size, p))
        total = sum(e[1] for e in entries)
        for mtime, size, p in sorted(entries):
            if total <= budget:
                break
            _remove_quiet(p)
            total -= size
    except OSError:
        pass


def _guarded(loaded, jitted, path):
    """Wrap a prepared executable so failures degrade to the ordinary jit
    path instead of poisoning the run: anything on the first call (device
    topology drift, donation mismatch, a stale image) falls back
    permanently; a later TypeError (an aval change — e.g. reshaped scope
    state — that the pinned Compiled rejects but a jit retrace absorbs)
    falls back per call."""
    state = {"fn": None}

    def call(*args):
        fn = state["fn"]
        if fn is jitted:
            return jitted(*args)
        if fn is not None:
            try:
                return fn(*args)
            except TypeError:
                return jitted(*args)
        try:
            out = loaded(*args)
        except Exception:
            with _lock:
                _stats["aot_errors"] += 1
            _quarantine_aot(path)
            state["fn"] = jitted
            if any(
                getattr(leaf, "is_deleted", lambda: False)()
                for leaf in jax.tree_util.tree_leaves(args)
            ):
                # the failed dispatch already consumed donated buffers:
                # a retry would crash on deleted arrays — propagate the
                # real error instead of a confusing cascade
                raise
            return jitted(*args)
        state["fn"] = loaded
        return out

    return call


def prepare_executable(jitted, args, disk_key=None):
    """First-call hook for CompiledProgram/MultiStepProgram: given the
    jitted step function and the concrete call args, return the callable
    to use from now on — a deserialized AOT image on a warm start, or the
    (explicitly lowered+compiled, then serialized) fresh executable.
    Returns ``jitted`` unchanged when persistence is off, so the default
    path is byte-identical to before."""
    if configure() is None or disk_key is None:
        return jitted
    if jax.process_count() > 1:
        # multi-host executables bake in the global topology; the HLO-level
        # cache layer still applies, the AOT image layer does not
        return jitted
    path = _aot_path(disk_key, args)
    loaded = _load_aot(path)
    if loaded is not None:
        return _guarded(loaded, jitted, path)
    with _lock:
        _stats["aot_misses"] += 1
    try:
        compiled = jitted.lower(*args).compile()
    except Exception:
        # an AOT-path-only failure must not take down execution; the
        # plain jit call compiles the same computation its own way
        with _lock:
            _stats["aot_errors"] += 1
        return jitted
    _store_aot(path, compiled)
    # guarded: a Compiled is pinned to these exact avals, but the same
    # CompiledProgram may later be called with reshaped scope state —
    # the plain jit path retraces for that case, so fall back to it
    return _guarded(compiled, jitted, path)
