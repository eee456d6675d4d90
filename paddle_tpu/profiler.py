"""Profiler: host spans + compile events + async-fetch lifetimes in one
chrome trace, plus the step-telemetry surface.

Reference parity: python/paddle/fluid/profiler.py + platform/profiler.cc
(host events) + device_tracer.cc (CUPTI -> chrome trace via
tools/timeline.py). On TPU, device timelines come from jax.profiler
(XPlane -> TensorBoard/perfetto); the host-side RecordEvent/report table
is reimplemented here, and chrome-trace export is native.

Trace unification (the flight-recorder PR): every recorded span carries a
process-unique span id and the REAL thread id (run_async nan-check /
donation work happens off the main thread), compile events observed by
core/exec_cache.py's jax.monitoring taps land in the same stream (cat
``compile``), and async fetches appear as perfetto nestable async spans
(dispatch -> ready -> materialize, cat ``async_fetch``). When a
jax.profiler trace session is active, RecordEvent also opens a
``jax.profiler.TraceAnnotation`` so the device XPlanes line up with the
host spans in the merged view.

The report is routed through ``logging`` (logger
``paddle_tpu.profiler``); pass ``print_report=True`` to get the classic
stdout table — pytest runs stay quiet by default.
"""

import contextlib
import json
import logging
import os
import threading

from paddle_tpu.observability import lock_witness
import time
from collections import defaultdict

__all__ = [
    "cuda_profiler",
    "reset_profiler",
    "profiler",
    "start_profiler",
    "stop_profiler",
    "RecordEvent",
    "exec_cache_stats",
    "step_stats",
    "memory_stats",
    "record_span",
]

logger = logging.getLogger("paddle_tpu.profiler")

_lock = lock_witness.make_lock("profiler")
_state = {
    "enabled": False,
    "events": [],   # dicts: name, start, end, tid, span_id, cat, args
    "async": [],    # dicts: name, span_id, dispatch, ready, end, tid
    "jax_trace_dir": None,
}
_span_counter = [0]


def enabled():
    return _state["enabled"]


def _next_span_id():
    with _lock:
        _span_counter[0] += 1
        return _span_counter[0]


def record_span(name, start, end, cat="host", args=None, tid=None):
    """Append one completed span to the trace stream (thread-safe). Used
    by RecordEvent, the executors, and core/exec_cache.py's compile taps;
    no-op when the profiler is off."""
    if not _state["enabled"]:
        return None
    span = {
        "name": name,
        "start": start,
        "end": end,
        "tid": tid if tid is not None else threading.get_ident(),
        "span_id": _next_span_id(),
        "cat": cat,
        "args": args,
    }
    with _lock:
        _state["events"].append(span)
    return span["span_id"]


# -- async-fetch lifetimes ---------------------------------------------------

def async_fetch_begin(fetch_names):
    """Dispatch point of a run_async: returns a tracking dict the
    FetchHandle threads through its lifetime, or None when the profiler
    is off (the FetchHandle hot path guards on that None)."""
    if not _state["enabled"]:
        return None
    track = {
        "name": "async_fetch[%s]" % ",".join(map(str, fetch_names[:4])),
        "span_id": _next_span_id(),
        "dispatch": time.perf_counter(),
        "ready": None,
        "end": None,
        "tid": threading.get_ident(),
    }
    with _lock:
        _state["async"].append(track)
    return track


def async_fetch_ready(track):
    if track is not None and track["ready"] is None:
        track["ready"] = time.perf_counter()


def async_fetch_end(track):
    if track is not None and track["end"] is None:
        if track["ready"] is None:
            track["ready"] = time.perf_counter()
        track["end"] = time.perf_counter()


class RecordEvent(object):
    """RAII host event (platform/profiler.h:100 RecordEvent parity).
    Thread-correct: concurrent scopes on different threads record their
    own tids. Under an active jax trace session, also opens a
    TraceAnnotation so device XPlanes carry the same name."""

    def __init__(self, name):
        self.name = name
        self._start = None
        self._annotation = None

    def __enter__(self):
        if _state["enabled"]:
            if _state["jax_trace_dir"]:
                try:
                    import jax

                    self._annotation = jax.profiler.TraceAnnotation(
                        self.name)
                    self._annotation.__enter__()
                except Exception:
                    self._annotation = None
            self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            try:
                self._annotation.__exit__(*exc)
            except Exception:
                pass
            self._annotation = None
        if _state["enabled"] and self._start is not None:
            record_span(self.name, self._start, time.perf_counter())
        return False


def reset_profiler():
    with _lock:
        _state["events"] = []
        _state["async"] = []


def start_profiler(state="All", trace_dir=None):
    _state["enabled"] = True
    reset_profiler()
    if trace_dir:
        import jax

        _state["jax_trace_dir"] = trace_dir
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key="total", profile_path="/tmp/profile",
                  print_report=False):
    """Stop, report, export. The report goes to the ``paddle_tpu.profiler``
    logger (INFO); ``print_report=True`` additionally prints the classic
    stdout table. The chrome trace always lands at ``profile_path``."""
    _state["enabled"] = False
    if _state["jax_trace_dir"]:
        import jax

        jax.profiler.stop_trace()
        _state["jax_trace_dir"] = None
    _emit_report(sorted_key, print_report)
    _emit_exec_cache_report(print_report)
    _write_chrome_trace(profile_path)


def exec_cache_stats():
    """Executable-cache counters (core/exec_cache.py): compile seconds
    split cold/warm, persistent-cache and AOT-image hit/miss counts, and
    ``fresh_compiles`` — the XLA compiles no cache layer could serve."""
    from paddle_tpu.core import exec_cache

    return exec_cache.stats()


def step_stats(peak=None):
    """Per-step percentiles (p50/p95/p99) + MFU estimate from the step
    telemetry ring (observability/telemetry.py). Needs FLAGS_telemetry=1
    (or telemetry.enable()) while the steps ran."""
    from paddle_tpu.observability import telemetry

    return telemetry.step_stats(peak=peak)


def memory_stats():
    """Predicted-vs-measured HBM report (observability/memory.py).

    ``measured_peak_bytes`` is the high-water mark of ledger-tracked
    bytes over the recorded step window (max of the per-record
    watermarks, falling back to the current live total);
    ``predicted_peak_bytes`` is the largest registered memory-plan peak,
    with the plan detail (op, top tensors) under ``predicted_plan``.
    Needs FLAGS_telemetry=1 (or telemetry.enable()) while the steps ran;
    with telemetry off this is a pull-based read of empty state — zero
    hot-path overhead either way."""
    from paddle_tpu.observability import memory, telemetry

    recs = telemetry.step_records()
    measured = max(
        (r.get("peak_hbm_bytes", 0) for r in recs), default=0)
    measured = measured or memory.live_bytes() or None
    plans = memory.plans()
    predicted = max(
        (p["peak_bytes"] for p in plans.values()), default=0) or None
    # a derived-sharding plan predicts PER-DEVICE residency
    # (shard_factors divide each var); the measured watermark sums every
    # ledger label across the mesh — scale by the plan's device count so
    # the ratio stays apples-to-apples (exact for sharded vars, an
    # underestimate for replicated ones)
    predicted_scaled = max(
        (p["peak_bytes"] * p.get("mesh_devices", 1)
         for p in plans.values()), default=0) or None
    out = {
        "live_bytes": memory.live_bytes(),
        "live_by_kind": memory.live_by_kind(),
        "live_by_device": memory.live_by_device(),
        "measured_peak_bytes": measured,
        "predicted_peak_bytes": predicted,
        "predicted_plan": memory.last_plan(),
        "top_holders": memory.top_holders(5),
        "plans_registered": len(plans),
    }
    if measured and predicted_scaled:
        out["predicted_over_measured"] = round(
            float(predicted_scaled) / float(measured), 4)
    return out


def _emit_exec_cache_report(print_report):
    st = exec_cache_stats()
    if not (st["backend_compiles"] or st["aot_hits"] or st["aot_misses"]):
        return
    msg = (
        "Executable cache: %d fresh compile(s), %d persistent hit(s), "
        "%d AOT image hit(s); compile %.3fs cold / %.3fs warm%s"
        % (
            st["fresh_compiles"], st["persistent_hits"], st["aot_hits"],
            st["compile_seconds_cold"], st["compile_seconds_warm"],
            " (xla cache: %s; aot images: %s)"
            % (st["xla_cache_dir"] or "off", st["cache_dir"] or "off"),
        )
    )
    logger.info("%s", msg)
    if print_report:
        print(msg)


def _emit_report(sorted_key, print_report):
    with _lock:
        events = list(_state["events"])
    agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
    for ev in events:
        dt = (ev["end"] - ev["start"]) * 1000.0
        a = agg[ev["name"]]
        a[0] += 1
        a[1] += dt
        a[2] = min(a[2], dt)
        a[3] = max(a[3], dt)
    if not agg:
        return
    rows = [
        (name, c, tot, tot / c, mn, mx)
        for name, (c, tot, mn, mx) in agg.items()
    ]
    keyfn = {
        "calls": lambda r: -r[1],
        "total": lambda r: -r[2],
        "ave": lambda r: -r[3],
        "min": lambda r: r[4],
        "max": lambda r: -r[5],
    }.get(sorted_key, lambda r: -r[2])
    rows.sort(key=keyfn)
    lines = [
        "------------------------->     Profiling Report     <-------------------------",
        "%-40s %8s %12s %12s %12s %12s" % ("Event", "Calls", "Total(ms)", "Avg(ms)", "Min(ms)", "Max(ms)"),
    ]
    for name, c, tot, avg, mn, mx in rows:
        lines.append("%-40s %8d %12.4f %12.4f %12.4f %12.4f"
                     % (name, c, tot, avg, mn, mx))
    report = "\n".join(lines)
    logger.info("%s", report)
    if print_report:
        print(report)


def _write_chrome_trace(path):
    """tools/timeline.py-equivalent chrome trace export, unified: host
    spans + compile spans (X events on their recording thread), async
    fetches as perfetto nestable async spans (b/n/e sharing an id), and
    thread-name metadata so perfetto's rows read as real threads."""
    with _lock:
        events = list(_state["events"])
        asyncs = [dict(a) for a in _state["async"]]
    if not events and not asyncs:
        return
    pid = os.getpid()
    t0 = min(
        [e["start"] for e in events] + [a["dispatch"] for a in asyncs]
    )

    def us(t):
        return (t - t0) * 1e6

    out = []
    tids = {}
    for e in events:
        tids.setdefault(e["tid"], len(tids))
        out.append({
            "name": e["name"],
            "cat": e["cat"],
            "ph": "X",
            "ts": us(e["start"]),
            "dur": (e["end"] - e["start"]) * 1e6,
            "pid": pid,
            "tid": e["tid"],
            "args": dict(e["args"] or {}, span_id=e["span_id"]),
        })
    for a in asyncs:
        tids.setdefault(a["tid"], len(tids))
        end = a["end"] if a["end"] is not None else a["dispatch"]
        ready = a["ready"] if a["ready"] is not None else end
        base = {"cat": "async_fetch", "pid": pid, "tid": a["tid"],
                "id": a["span_id"]}
        out.append(dict(base, name=a["name"], ph="b",
                        ts=us(a["dispatch"])))
        out.append(dict(base, name="ready", ph="n", ts=us(ready)))
        out.append(dict(base, name=a["name"], ph="e", ts=us(end)))
    main_tid = threading.main_thread().ident
    for tid, idx in sorted(tids.items(), key=lambda kv: kv[1]):
        label = "main" if tid == main_tid else "thread-%d" % idx
        out.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": label},
        })
    try:
        with open(path, "w") as f:
            json.dump({"traceEvents": out}, f)
    except OSError:
        pass


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path="/tmp/profile",
             trace_dir=None, print_report=False):
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path, print_report=print_report)


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """No CUDA on TPU; kept for API parity — delegates to jax tracing."""
    yield
