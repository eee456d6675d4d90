#!/usr/bin/env python
"""Render request traces: per-request waterfalls + Perfetto export.

The offline viewer over the ``<FLAGS_metrics_path>.traces.jsonl`` a
``FLAGS_request_tracing=1`` serving process leaves behind (one JSON line
per completed trace — ``observability/tracing.py``'s ring record). Three
views:

1. default — an ASCII waterfall per trace: every span on its own line,
   offset/duration in ms relative to the trace's first span, bar scaled
   to the request wall, key meta inline (tokens, cow_copies,
   prefix_hit_pages, speculative) and the derived SLO stats underneath
   (TTFT, queue/prefill/decode split, inter-token p50/p95, page-seconds,
   speculation fraction, span coverage, and the decode worker's rounds
   the request queued and decoded through).
2. ``--slowest N`` — only the N slowest requests by wall time (the
   "which request blew the p99" workflow: the serving histogram's bucket
   exemplar names a trace id, ``--trace`` pulls its waterfall).
3. ``--perfetto OUT`` — Chrome/Perfetto trace JSON
   (``{"traceEvents": [...]}``; load in ui.perfetto.dev or
   chrome://tracing) with one track per request.

Usage::

    python tools/trace_view.py /tmp/m.traces.jsonl
    python tools/trace_view.py /tmp/m.traces.jsonl --slowest 3
    python tools/trace_view.py /tmp/m.traces.jsonl --trace 1f2e3d4c5b6a7988
    python tools/trace_view.py /tmp/m.traces.jsonl --perfetto /tmp/t.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BAR_W = 40
# meta keys worth a column in the waterfall line (everything else is in
# the Perfetto export's args)
_META_KEYS = ("tokens", "cow_copies", "prefix_hit_pages", "speculative",
              "kind", "members", "batch", "force_closed", "round")


def _rounds(spans):
    """(decode-worker rounds the request waited in the queue, rounds it
    decoded in), from the ``round=`` its spans carry; None where a span
    fell into no round (a session driven without a frontend)."""
    at = {}
    for sp in spans:
        rnd = (sp.get("meta") or {}).get("round")
        if rnd is not None:
            at.setdefault(sp["name"], []).append(rnd)
    admitted = at.get("prefill") or at.get("admit")
    waited = (admitted[0] - at["queue"][0]
              if admitted and "queue" in at else None)
    decoded = len(set(at["decode.step"])) if "decode.step" in at else None
    return waited, decoded


def _load_traces_jsonl(path):
    """Trace records or a friendly exit — a missing/empty snapshot means
    tracing was off or the path is wrong, not a stack trace."""
    if not os.path.exists(path):
        sys.exit(
            "trace_view: %s does not exist.\nRun the serving workload "
            "with FLAGS_request_tracing=1, FLAGS_telemetry=1 and "
            "FLAGS_metrics_path=<p> (completed traces land at "
            "<p>.traces.jsonl), or pass that .traces.jsonl path here."
            % path)
    recs = []
    with open(path) as f:
        for line in f:
            if line.strip():
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    pass
    if not recs:
        sys.exit(
            "trace_view: %s is empty — the process completed no traced "
            "request (was FLAGS_request_tracing=1? did any request "
            "finish before the telemetry flush?)" % path)
    return recs


def _fmt_meta(meta):
    parts = ["%s=%s" % (k, meta[k]) for k in _META_KEYS
             if meta.get(k) is not None]
    return (" " + " ".join(parts)) if parts else ""


def _waterfall(rec):
    """One trace's ASCII waterfall: spans sorted by start, bar position
    scaled to the request wall."""
    spans = sorted(rec.get("spans", ()), key=lambda s: s["t0"])
    stats = rec.get("stats") or {}
    if not spans:
        print("trace %s: no spans" % rec.get("trace_id"))
        return
    t_base = spans[0]["t0"]
    t_end = max(s["t1"] for s in spans if s["t1"] is not None)
    wall = max(t_end - t_base, 1e-9)
    print("trace %s  endpoint=%s origin=%s outcome=%s  wall=%.1fms "
          "spans=%d" % (rec.get("trace_id"), rec.get("endpoint"),
                        rec.get("origin"), rec.get("outcome"),
                        wall * 1e3, len(spans)))
    for sp in spans:
        t0 = sp["t0"] - t_base
        t1 = (sp["t1"] if sp["t1"] is not None else t_end) - t_base
        lo = int(round(t0 / wall * BAR_W))
        hi = max(lo + 1, int(round(t1 / wall * BAR_W)))
        bar = " " * lo + "#" * min(hi - lo, BAR_W - lo)
        print("  %-12s |%-*s| %9.3fms +%9.3fms%s"
              % (sp["name"], BAR_W, bar, (t1 - t0) * 1e3, t0 * 1e3,
                 _fmt_meta(sp.get("meta") or {})))
    line = ["  stats:"]
    for key in ("ttft_s", "queue_s", "prefill_s", "decode_s",
                "flush_s"):
        if stats.get(key) is not None:
            line.append("%s=%.3fms" % (key[:-2], stats[key] * 1e3))
    for key, fmt in (("intertoken_p50_ms", "itl_p50=%.3fms"),
                     ("intertoken_p95_ms", "itl_p95=%.3fms"),
                     ("page_seconds", "page_s=%.4f"),
                     ("spec_fraction", "spec=%.2f"),
                     ("span_coverage", "coverage=%.4f")):
        if stats.get(key) is not None:
            line.append(fmt % stats[key])
    if stats.get("tokens"):
        line.append("tokens=%d" % stats["tokens"])
    # round ids count every pass of the worker, so a difference of ids
    # is a number of passes
    for key, val in zip(("queue_rounds", "decode_rounds"), _rounds(spans)):
        if val is not None:
            line.append("%s=%d" % (key, val))
    print(" ".join(line))


def _write_perfetto(recs, out_path):
    from paddle_tpu.observability import tracing

    events = []
    for row, rec in enumerate(recs):
        events.extend(tracing.perfetto_events(rec, row=row, pid=1))
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    print("trace_view: wrote %d events for %d traces -> %s"
          % (len(events), len(recs), out_path))


def main():
    ap = argparse.ArgumentParser(
        description="per-request trace waterfalls + Perfetto export")
    ap.add_argument("traces", help="path to a .traces.jsonl snapshot")
    ap.add_argument("--slowest", type=int, default=None, metavar="N",
                    help="only the N slowest requests by wall time")
    ap.add_argument("--trace", default=None, metavar="TID",
                    help="only the request with this trace id")
    ap.add_argument("--perfetto", default=None, metavar="OUT",
                    help="also write Chrome/Perfetto trace JSON here")
    args = ap.parse_args()

    recs = _load_traces_jsonl(args.traces)
    if args.trace:
        recs = [r for r in recs if r.get("trace_id") == args.trace]
        if not recs:
            sys.exit("trace_view: trace id %s not in %s (aged out of "
                     "the completed-trace ring before the flush?)"
                     % (args.trace, args.traces))
    if args.slowest is not None:
        recs = sorted(recs, key=lambda r: -(r.get("stats") or {})
                      .get("wall_s", 0.0))[:max(0, args.slowest)]
    for i, rec in enumerate(recs):
        if i:
            print()
        _waterfall(rec)
    if args.perfetto:
        _write_perfetto(recs, args.perfetto)


if __name__ == "__main__":
    main()
