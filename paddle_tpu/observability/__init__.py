"""Observability: the flight recorder for the XLA execution engine.

PR 1 added three cache layers plus async dispatch whose behavior was
visible only through one hand-rolled print report. This package makes the
framework self-describing in production instead:

* ``metrics_registry`` — a process-global, thread-safe registry of
  counters / gauges / fixed-bucket histograms with label support,
  exported as Prometheus text format and JSONL snapshots
  (``FLAGS_metrics_path``). The executable-cache counters
  (``core/exec_cache.py``) are absorbed via a collector, so one scrape
  carries the whole compile-tax story.
* ``telemetry`` — per-step flight data recorded by ``Executor.run`` /
  ``run_async`` / ``run_multi_step`` and ``ParallelExecutor.run``: wall
  time, feed/fetch bytes, host->device transfer time, device memory in
  use, and an MFU/roofline estimate from per-fingerprint FLOP counts.
  Surfaced through ``profiler.step_stats()`` percentiles and a
  ``StepTimer`` callback API. Switched by ``FLAGS_telemetry`` (module
  bool guard: zero overhead when off).
* ``explain`` — the recompile explainer: every fresh XLA trace logs a
  structured event naming which cache-key component changed vs. the
  nearest cached entry, so "why did it retrace" is one log line.

The failure-forensics layer (this PR) covers the moments the healthy-path
recorder can't:

* ``blackbox`` — a bounded ring of flight events (dispatches with feed
  specs/fetch lists, exceptions, notes) dumped as one JSON file on
  unhandled executor/Predictor exceptions, fatal signals, watchdog
  hangs, or demand (``FLAGS_blackbox_path``;
  ``tools/blackbox_dump.py`` pretty-prints it).
* ``watchdog`` — opt-in background hang detector: no executor/fetch
  progress within ``FLAGS_watchdog_timeout`` (default: a multiple of
  telemetry's p95 step time) dumps all thread stacks + the black box,
  then optionally aborts (``FLAGS_watchdog_abort``).
* ``nan_provenance`` — when ``FLAGS_check_nan_inf``'s on-device scan
  trips, the step is replayed per-op from a pre-step snapshot and the
  FIRST op with a non-finite output is blamed as an
  ``analysis.diagnostics.Diagnostic`` (rule N001) with a fix hint.

The memory layer (this PR) makes HBM first-class alongside time and
failures:

* ``memory`` — a live-buffer ledger the executors/feed/fetch/cache/
  checkpoint paths write (``paddle_tpu_hbm_live_bytes{device,kind}``,
  per-step ``peak_hbm_bytes`` watermarks in the telemetry records), a
  predicted-memory planner over the PR 3 liveness analysis
  (``Program.memory_plan``, ``profiler.memory_stats()`` for
  predicted-vs-measured), and OOM forensics: RESOURCE_EXHAUSTED dispatch
  deaths become rule **M001** diagnostics — never retried — whose
  black-box dump names the top holders and the predicted peak.

The training plane adds phase-level attribution:

* ``step_profiler`` — the training-step observatory
  (``FLAGS_step_profile``): every step becomes a phase-attributed
  record (input wait / feed / compile / dispatch / device / fetch /
  host residual) joined against tools/hlo_cost_model.py's fused-group
  roofline — achieved-FLOP/s, achieved-MFU, predicted-vs-achieved and
  an input/host/compute/bandwidth-bound verdict per step — plus an
  online median+MAD regression detector that names the guilty phase.
  Ring + ``<metrics_path>.stepprof.jsonl``; ``tools/step_breakdown.py
  --steps`` is the offline view.

The serving plane adds request-scoped attribution:

* ``tracing`` — one trace per serving request (id minted by
  ``ServingClient``, carried in the wire envelope, continued by the
  frontend and decode session): span waterfalls covering queue wait,
  admission, prefill, every decode dispatch and wire flush, with
  derived SLO stats (TTFT, inter-token distribution, page-seconds,
  speculation fraction). Completed traces land in a bounded ring +
  ``<metrics_path>.traces.jsonl``; latency histograms carry trace-id
  exemplars; blackbox dumps list in-flight ids. Switched by
  ``FLAGS_request_tracing`` (module-bool guard, telemetry's contract).

``docs/OBSERVABILITY.md`` is the operator's guide (metric catalog, how
to read the explainer, loading the merged trace in perfetto, failure
forensics, the memory ledger).
"""

from paddle_tpu.observability import blackbox  # noqa: F401
from paddle_tpu.observability import explain  # noqa: F401
from paddle_tpu.observability import memory  # noqa: F401
from paddle_tpu.observability import metrics_registry  # noqa: F401
from paddle_tpu.observability import nan_provenance  # noqa: F401
from paddle_tpu.observability import step_profiler  # noqa: F401
from paddle_tpu.observability import telemetry  # noqa: F401
from paddle_tpu.observability import tracing  # noqa: F401
from paddle_tpu.observability import watchdog  # noqa: F401
from paddle_tpu.observability.metrics_registry import REGISTRY  # noqa: F401


def _exec_cache_collector():
    """Scrape-time view of the executable-cache counters: the single
    source of truth stays core/exec_cache.py (perfbench/ and the tests
    read it directly); the registry mirrors it so one Prometheus
    scrape carries compile-tax data without double bookkeeping."""
    from paddle_tpu.core import exec_cache

    st = exec_cache.stats()
    yield ("paddle_tpu_fresh_compiles_total", "counter",
           "XLA compiles no cache layer could serve",
           [({}, st["fresh_compiles"])])
    yield ("paddle_tpu_backend_compiles_total", "counter",
           "XLA backend compile calls observed (jax.monitoring)",
           [({}, st["backend_compiles"])])
    yield ("paddle_tpu_exec_cache_hits_total", "counter",
           "executable-cache hits by layer",
           [({"layer": "trace"}, st["trace_cache_hits"]),
            ({"layer": "persistent"}, st["persistent_hits"]),
            ({"layer": "aot"}, st["aot_hits"])])
    yield ("paddle_tpu_exec_cache_misses_total", "counter",
           "executable-cache misses by layer",
           [({"layer": "trace"}, st["trace_cache_misses"]),
            ({"layer": "persistent"}, st["persistent_misses"]),
            ({"layer": "aot"}, st["aot_misses"])])
    yield ("paddle_tpu_exec_cache_errors_total", "counter",
           "corrupt/incompatible persistent entries tolerated",
           [({"layer": "aot"}, st["aot_errors"])])
    yield ("paddle_tpu_compile_seconds_total", "counter",
           "wall seconds inside XLA compiles, split cold/warm",
           [({"kind": "cold"}, st["compile_seconds_cold"]),
            ({"kind": "warm"}, st["compile_seconds_warm"])])


REGISTRY.register_collector(_exec_cache_collector)
