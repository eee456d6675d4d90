"""Step telemetry: per-step flight data for the executors.

``Executor.run`` / ``run_async`` / ``run_multi_step`` and
``ParallelExecutor.run`` call :func:`record_step` with wall time,
feed/fetch byte counts, host->device transfer seconds and the compiled
program's structural fingerprint. Each record lands in a bounded ring
buffer (for ``step_stats`` percentiles and the JSONL snapshot) and in the
process metrics registry (for the Prometheus scrape).

MFU: executors register analytic FLOP counts per compiled executable
(:func:`register_flops`, keyed by ``cp._exec_cache_key``; the estimate
reuses tools/hlo_cost_model.py's jaxpr walker over the exact traced step
function, run AFTER the first timed step), so ``step_stats()['mfu']`` is
sum(flops)/sum(wall)/peak over the recorded window — the
roofline-accounting discipline TPU codesign work leans on.

Overhead contract: every hook in the executors guards on the module-level
bool ``ENABLED`` (one attribute load, no dict lookups, no function call)
so the hot path with telemetry off is unchanged. ``FLAGS_telemetry=1``
turns it on at import; :func:`enable` flips it at runtime.
"""

import atexit
import collections
import threading
import time

from paddle_tpu.observability import memory as _memory
from paddle_tpu.observability import lock_witness
from paddle_tpu.observability.metrics_registry import REGISTRY

__all__ = [
    "ENABLED", "enable", "reset", "record_step", "register_flops",
    "step_stats", "step_records", "add_step_callback",
    "remove_step_callback", "StepTimer", "record_fetch_materialize",
    "flush", "estimate_flops", "device_memory_bytes", "peak_flops",
    "chip_peaks", "CHIP_PEAKS",
    "executable_fingerprint", "capture_step_avals",
    "register_flops_from_avals", "record_device_steps",
    "record_device_transfer", "record_pipeline_occupancy",
    "device_step_times", "device_label",
]

ENABLED = False

_RING_CAP = 4096

_lock = lock_witness.make_lock("observability.telemetry")
_records = collections.deque(maxlen=_RING_CAP)
_flops = {}              # fingerprint -> flops per step
_callbacks = []

# THE peak table — the only one: published per-chip peaks keyed by the
# ``device_kind`` JAX reports (lowercased). peak_flops() below, the
# step profiler's roofline join and tools/hlo_cost_model.py all read
# it. Source: Google Cloud TPU documentation, system-architecture pages
# ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM; "TPU v4": 275 TFLOP/s bf16;
# "TPU v6e": 918 TFLOP/s bf16). A device that is not here HAS NO PEAK:
# its MFU and roofline share are "not measured", never another chip's
# nameplate.
ChipPeaks = collections.namedtuple(
    "ChipPeaks", ["bf16_flops", "hbm_bytes_per_sec"])
CHIP_PEAKS = {
    "tpu v5 lite": ChipPeaks(197e12, 819e9),
    "tpu v4": ChipPeaks(275e12, None),
    "tpu v6 lite": ChipPeaks(918e12, None),
}

# step-time buckets: 100us .. 100s (training steps span ms..minutes)
_STEP_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                 50.0, 100.0)
# async-fetch materialize: dominated by device wait + d2h transfer
_FETCH_BUCKETS = (0.00001, 0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                  0.5, 1.0, 5.0, 10.0)

_steps_total = REGISTRY.counter(
    "paddle_tpu_steps_total", "program steps executed", labels=("executor",))
_step_seconds = REGISTRY.histogram(
    "paddle_tpu_step_seconds", "per-step wall time (seconds)",
    labels=("executor",), buckets=_STEP_BUCKETS)
_feed_bytes = REGISTRY.counter(
    "paddle_tpu_feed_bytes_total", "bytes fed host->device")
_fetch_bytes = REGISTRY.counter(
    "paddle_tpu_fetch_bytes_total", "bytes fetched device->host")
_h2d_seconds = REGISTRY.counter(
    "paddle_tpu_h2d_seconds_total", "wall seconds in feed transfers")
_fetch_materialize = REGISTRY.histogram(
    "paddle_tpu_fetch_materialize_seconds",
    "async-fetch dispatch-to-numpy latency", buckets=_FETCH_BUCKETS)
_device_mem = REGISTRY.gauge(
    "paddle_tpu_device_bytes_in_use",
    "device memory in use, summed over all local devices (bytes)")
# -- per-device series (the multichip incident-response surface): one
# labeled series per local device, plus a straggler ratio. All written
# only from the telemetry-guarded paths — zero cost with the flag off.
_device_mem_per = REGISTRY.gauge(
    "paddle_tpu_device_bytes_in_use_per_device",
    "device memory in use, one series per local device (bytes)",
    labels=("device",))
_device_step_seconds = REGISTRY.gauge(
    "paddle_tpu_device_step_seconds",
    "last dispatch->shard-ready latency per device (seconds)",
    labels=("device",))
_device_transfer = REGISTRY.counter(
    "paddle_tpu_device_transfer_bytes_total",
    "feed bytes landed per device (addressable shard sizes)",
    labels=("device",))
_straggler = REGISTRY.gauge(
    "paddle_tpu_device_step_imbalance",
    "straggler ratio: max/median per-device step time of the last "
    "recorded parallel step (1.0 = perfectly balanced)")
_stage_occupancy = REGISTRY.gauge(
    "paddle_tpu_pipeline_stage_occupancy",
    "fraction of schedule ticks each pipeline stage does useful work "
    "(M/(M+S-1) for a GPipe schedule)", labels=("stage",))
_hbm_peak = REGISTRY.gauge(
    "paddle_tpu_hbm_peak_bytes",
    "per-step high-water mark of ledger-tracked live bytes "
    "(observability/memory.py watermark of the last recorded step)")


def enable(on=True):
    """Flip telemetry at runtime (tests, notebooks). The flag only sets
    the import-time default. The live-buffer ledger
    (observability/memory.py) switches in lockstep — memory accounting
    is part of the same flight recorder and the same overhead contract."""
    global ENABLED
    ENABLED = bool(on)
    _memory.enable(ENABLED)
    return ENABLED


def _init_from_flags():
    from paddle_tpu import flags

    try:
        enable(flags.get("telemetry"))
    except KeyError:  # pragma: no cover - flag table always has it
        pass


def reset(flops=False):
    """Drop the ring buffer (phase-scoped measurement, e.g.
    tools/step_breakdown.py). The per-fingerprint FLOP table survives by
    default — executables register it once per compile
    (cp._telemetry_flops_done), so clearing it would leave MFU None for
    the rest of the process; pass ``flops=True`` only when also tearing
    down the compiled programs (tests)."""
    with _lock:
        _records.clear()
        if flops:
            _flops.clear()


def register_flops(fingerprint, flops):
    """Record the analytic FLOPs of one compiled step. The key must be
    per-EXECUTABLE (``cp._exec_cache_key``: structural fingerprint x feed
    specs x fetch set), not per-program: two feed shapes of one program
    do different FLOPs, and a program-level key would let the last
    compile's count misprice every other shape's steps."""
    if fingerprint and flops:
        with _lock:
            _flops[fingerprint] = float(flops)


def executable_fingerprint(cp, program=None):
    """The telemetry key for one compiled executable: its cross-process
    cache key when stamped (always, for executor-built programs), else
    the program's structural fingerprint."""
    key = getattr(cp, "_exec_cache_key", None)
    if key:
        return key
    if program is not None:
        from paddle_tpu.core.fingerprint import program_fingerprint

        return program_fingerprint(program)
    return None


def step_avals(cp, state, feeds, key):
    """The avals of one call of ``cp.jitted``: (mutable state, frozen
    state, feeds, the pair (base key, run counter))."""
    import jax

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return (
        {n: aval(state[n]) for n in cp.mutable_state},
        {n: aval(state[n]) for n in cp.frozen_state},
        {n: aval(v) for n, v in feeds.items()},
        jax.tree_util.tree_map(aval, key),
    )


def capture_step_avals(cp, state, feeds, key):
    """Aval snapshot for the deferred FLOP estimate, taken BEFORE the
    step call (which donates the mutable state buffers). One-shot per
    executable via ``cp._telemetry_flops_done``; returns None when
    already registered. Shared by Executor and ParallelExecutor."""
    if getattr(cp, "_telemetry_flops_done", False):
        return None
    cp._telemetry_flops_done = True
    return step_avals(cp, state, feeds, key)


def register_flops_from_avals(cp, fingerprint, avals, steps=1):
    """Run the (re-trace) FLOP estimate and file it — call AFTER the
    timed step so the trace never pollutes the recorded wall time."""
    est = estimate_flops(cp.jitted, avals)
    if est:
        register_flops(fingerprint, est / max(1, steps))


def add_step_callback(fn):
    """Trainer hook: ``fn(record_dict)`` runs after every recorded step
    (loss-curve dashboards, slow-step alarms). Exceptions are swallowed —
    a broken callback must not take down training."""
    with _lock:
        if fn not in _callbacks:
            _callbacks.append(fn)


def remove_step_callback(fn):
    with _lock:
        if fn in _callbacks:
            _callbacks.remove(fn)


def device_label(d):
    """THE stable per-device metric label ('tpu:3', 'cpu:0'), matching
    the explainer's device component. Single definition — mesh.py
    re-exports it — so per-device series from telemetry, transfer and
    mesh metrics always join on the same key."""
    return "%s:%d" % (d.platform, d.id)


_device_label = device_label


def device_memory_bytes(per_device=False):
    """Bytes in use summed over ALL local devices (the old behavior
    sampled only device 0 — on a multichip mesh that under-reported by
    the device count and hid per-chip OOM pressure). ``per_device=True``
    returns a {label: bytes} dict instead. None / {} when the backend
    does not report (CPU, older runtimes)."""
    out = {}
    try:
        import jax

        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats:
                out[_device_label(d)] = int(stats.get("bytes_in_use", 0))
    except Exception:
        pass
    if per_device:
        return out
    return sum(out.values()) or None


def device_step_times(arrays, t_dispatch):
    """Per-device dispatch->ready latency of one parallel step.

    Walks the first fetched/state array that has addressable shards and
    blocks on each device's shard in turn, recording the elapsed time at
    which it became ready. A healthy mesh returns near-identical times;
    a straggling chip shows up as the max. (Sequential blocking means a
    device that finished earlier than the one before it reads as that
    earlier wall — the MAX and the imbalance ratio are exact, the
    per-device floor is an upper bound. Good enough to NAME the
    straggler, which is the incident-response question.)"""
    import jax

    times = {}
    for a in arrays:
        if not isinstance(a, jax.Array):
            continue
        try:
            shards = a.addressable_shards
        except Exception:
            continue
        if len(shards) < 2:
            continue
        for sh in shards:
            label = _device_label(sh.device)
            if label not in times:
                sh.data.block_until_ready()
                times[label] = time.perf_counter() - t_dispatch
        if times:
            break
    return times


def record_device_steps(times):
    """File one parallel step's per-device ready times (seconds) into
    the labeled gauge, and refresh the straggler ratio (max/median)."""
    if not times:
        return None
    for label, t in times.items():
        _device_step_seconds.set(t, device=label)
    vals = sorted(times.values())
    mid = len(vals) // 2
    median = vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2.0
    ratio = (vals[-1] / median) if median > 0 else 1.0
    _straggler.set(ratio)
    return ratio


def record_device_transfer(bytes_by_device):
    """Count feed bytes against the device that received them
    (``{label: bytes}`` — how much of the host->device transfer each
    chip actually took, the lens that catches a feed pipeline sending a
    replicated tensor it meant to shard)."""
    for label, b in (bytes_by_device or {}).items():
        if b:
            _device_transfer.inc(int(b), device=label)


def record_pipeline_occupancy(n_stages, n_micro):
    """GPipe schedule occupancy: each stage does useful work on M of the
    M+S-1 ticks. One labeled series per stage so dashboards show the
    bubble fraction next to the per-device series."""
    n_stages, n_micro = int(n_stages), int(n_micro)
    if n_stages <= 0 or n_micro <= 0:
        return None
    occ = float(n_micro) / float(n_micro + n_stages - 1)
    for s in range(n_stages):
        _stage_occupancy.set(occ, stage="%d" % s)
    return occ


def record_step(executor, wall_s, steps=1, feed_bytes=0, fetch_bytes=0,
                h2d_seconds=0.0, fingerprint=None, dispatch_only=False,
                device_times=None):
    """One executed dispatch: ``steps`` program steps in ``wall_s``
    seconds (run_multi_step dispatches K at once). ``dispatch_only``
    marks async dispatches whose wall time is host latency, NOT step
    duration — they count in ``steps_total`` but are excluded from
    ``step_stats`` percentiles and MFU (a microsecond dispatch with a
    registered FLOP count would otherwise report MFU >> 1). Callers
    guard on ``ENABLED`` themselves; calling this directly always
    records."""
    steps = max(1, int(steps))
    per_step = wall_s / steps
    rec = {
        "ts": time.time(),
        "executor": executor,
        "wall_s": wall_s,
        "steps": steps,
        "step_s": per_step,
        "feed_bytes": int(feed_bytes),
        "fetch_bytes": int(fetch_bytes),
        "h2d_seconds": h2d_seconds,
        "fingerprint": fingerprint,
        "dispatch_only": bool(dispatch_only),
    }
    if device_times:
        rec["device_times"] = {k: float(v) for k, v in device_times.items()}
        record_device_steps(device_times)
    # HBM trajectory: the ledger's per-step watermark (measured), the
    # registered plan's prediction, and the top holders — so the step
    # JSONL carries the memory story tools/step_breakdown.py --memory
    # reads offline
    peak = _memory.take_step_peak()
    if peak:
        rec["peak_hbm_bytes"] = int(peak)
        _hbm_peak.set(peak)
    pred = _memory.predicted_peak(fingerprint)
    if pred:
        rec["predicted_peak_bytes"] = int(pred)
    top = _memory.top_holders(3)
    if top:
        rec["hbm_top"] = [[h["name"], h["kind"], h["bytes"]] for h in top]
    mem_per = device_memory_bytes(per_device=True)
    if mem_per:
        for label, b in mem_per.items():
            _device_mem_per.set(b, device=label)
        rec["device_bytes_in_use"] = sum(mem_per.values())
        _device_mem.set(rec["device_bytes_in_use"])
    with _lock:
        _records.append(rec)
        callbacks = list(_callbacks)
    _steps_total.inc(steps, executor=executor)
    _step_seconds.observe(per_step, executor=executor)
    if feed_bytes:
        _feed_bytes.inc(int(feed_bytes))
    if fetch_bytes:
        _fetch_bytes.inc(int(fetch_bytes))
    if h2d_seconds:
        _h2d_seconds.inc(h2d_seconds)
    for fn in callbacks:
        try:
            fn(dict(rec))
        except Exception:
            pass
    return rec


def record_fetch_materialize(seconds):
    """FetchHandle.result() latency: dispatch -> numpy in hand."""
    _fetch_materialize.observe(seconds)


def step_records():
    with _lock:
        return [dict(r) for r in _records]


def _percentile(sorted_vals, q):
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it (conservative, no interpolation)."""
    if not sorted_vals:
        return None
    import math

    k = max(0, min(len(sorted_vals) - 1,
                   int(math.ceil(q / 100.0 * len(sorted_vals))) - 1))
    return sorted_vals[k]


def chip_peaks(device=None):
    """The table's entry for ``device`` (default: this process's first
    device), or None for a device that is not in it (CPU included)."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    return CHIP_PEAKS.get((device.device_kind or "").lower())


def peak_flops(device=None):
    """Peak FLOP/s for MFU accounting: an explicit FLAGS_peak_tflops
    first, then the chip table; None for a device the table does not
    know — the caller then reports no MFU."""
    from paddle_tpu import flags

    override = float(flags.get("peak_tflops"))
    if override > 0:
        return override * 1e12
    peaks = chip_peaks(device)
    return peaks.bf16_flops if peaks else None


def step_stats(peak=None):
    """Percentiles + MFU over the recorded window.

    Returns ``{"count", "p50_ms", "p95_ms", "p99_ms", "mean_ms",
    "total_s", "flops_per_sec", "mfu", "peak_flops"}``. ``mfu`` is
    None when no recorded step has a registered FLOP count or no peak is
    known (pass ``peak`` in FLOP/s, or set ``FLAGS_peak_tflops``).
    """
    with _lock:
        recs = list(_records)
        flops_map = dict(_flops)
    # async dispatches measure host latency, not step time: they count,
    # but their wall must not enter percentiles or MFU
    timed = [r for r in recs if not r.get("dispatch_only")]
    per_step = sorted(r["step_s"] for r in timed)
    out = {
        "count": sum(r["steps"] for r in recs),
        "p50_ms": None, "p95_ms": None, "p99_ms": None, "mean_ms": None,
        "total_s": sum(r["wall_s"] for r in recs),
        "flops_per_sec": None, "mfu": None,
        "peak_flops": peak if peak else peak_flops(),
    }
    if per_step:
        out["p50_ms"] = _percentile(per_step, 50) * 1e3
        out["p95_ms"] = _percentile(per_step, 95) * 1e3
        out["p99_ms"] = _percentile(per_step, 99) * 1e3
        out["mean_ms"] = sum(per_step) / len(per_step) * 1e3
    known = [(r, flops_map[r["fingerprint"]]) for r in timed
             if r.get("fingerprint") in flops_map]
    if known:
        total_flops = sum(f * r["steps"] for r, f in known)
        total_wall = sum(r["wall_s"] for r, _ in known)
        if total_wall > 0:
            out["flops_per_sec"] = total_flops / total_wall
            if out["peak_flops"]:
                out["mfu"] = out["flops_per_sec"] / out["peak_flops"]
    return out


class StepTimer(object):
    """Context-manager hook for trainers driving their own loop::

        with telemetry.StepTimer("trainer", feed_bytes=nbytes):
            loss = exe.run(...)

    Records one step on exit (even when the body raises, so hung-step
    forensics still see the attempt's duration)."""

    def __init__(self, executor="trainer", steps=1, feed_bytes=0,
                 fetch_bytes=0, fingerprint=None):
        self.executor = executor
        self.steps = steps
        self.feed_bytes = feed_bytes
        self.fetch_bytes = fetch_bytes
        self.fingerprint = fingerprint
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        record_step(self.executor, time.perf_counter() - self._t0,
                    steps=self.steps, feed_bytes=self.feed_bytes,
                    fetch_bytes=self.fetch_bytes,
                    fingerprint=self.fingerprint)
        return False


# -- FLOP estimation ---------------------------------------------------------

def estimate_flops(fn, args):
    """Analytic FLOPs of one call of ``fn(*args)``: trace to a jaxpr and
    walk it with tools/hlo_cost_model.py's fusion-aware counter (DCE+CSE
    first — vjp re-traces would double-count the forward). Returns None
    on any failure; this is best-effort accounting, never load-bearing."""
    try:
        import jax

        from paddle_tpu.observability import _cost_model

        closed = jax.make_jaxpr(fn)(*args)
        jaxpr = closed.jaxpr
        # jit-wrapped fns trace to a single pjit eqn; unwrap so the
        # optimizer's top-level DCE+CSE sees the real op stream
        while (len(jaxpr.eqns) == 1
               and jaxpr.eqns[0].primitive.name in ("pjit", "jit")):
            inner = jaxpr.eqns[0].params.get("jaxpr")
            if inner is None:
                break
            jaxpr = getattr(inner, "jaxpr", inner)
        mod = _cost_model.load()
        return float(mod.sum_flops_recursive(mod.optimize_jaxpr(jaxpr)))
    except Exception:
        return None


# -- export ------------------------------------------------------------------

def write_steps_jsonl(path, mode="w"):
    """One JSON line per recorded step — the snapshot format
    tools/step_breakdown.py consumes."""
    import json

    recs = step_records()
    with open(path, mode) as f:
        for r in recs:
            f.write(json.dumps(r, sort_keys=True) + "\n")
    return len(recs)


def flush(metrics_path=None):
    """Write the Prometheus scrape to ``metrics_path`` (default:
    ``FLAGS_metrics_path``), the step JSONL next to it
    (``<path>.steps.jsonl``), and — when request tracing banked any
    completed traces — the trace JSONL (``<path>.traces.jsonl``, the
    file tools/trace_view.py and step_breakdown --requests consume).
    No-op when no path is configured."""
    if metrics_path is None:
        from paddle_tpu import flags

        try:
            metrics_path = flags.get("metrics_path")
        except KeyError:  # pragma: no cover
            metrics_path = ""
    if not metrics_path:
        return None
    REGISTRY.write_prometheus(metrics_path)
    write_steps_jsonl(metrics_path + ".steps.jsonl")
    from paddle_tpu.observability import tracing

    if tracing.completed():
        tracing.write_traces_jsonl(metrics_path + ".traces.jsonl")
    from paddle_tpu.observability import step_profiler

    if step_profiler.records():
        step_profiler.write_stepprof_jsonl(
            metrics_path + ".stepprof.jsonl")
    return metrics_path


@atexit.register
def _flush_at_exit():
    try:
        flush()
    except Exception:
        pass


_init_from_flags()
