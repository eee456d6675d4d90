"""Perf/memory regression sentry: diff bench / cost-model artifacts and
gate CI on the budget file.

The repo tracks its performance story in artifacts (``BENCH_*.json`` from
bench.py, cost-model JSONL from tools/hlo_cost_model.py) but until now
nothing STOPPED a PR from silently regressing step time, compile counts,
or HBM footprint. This tool is that gate, with the discipline the metrics
deserve:

* **Deterministic counters gate hard** — ``fresh_compiles`` (a +1 means
  the fingerprint cache broke for some path), ``predicted_peak_bytes``
  (the planner's number moves only when the program's liveness/shapes
  move), cost-model roofline time/bytes/flops. Any increase over the
  baseline/budget fails, no band.
* **Timings gate with a noise band** — step_ms percentiles, throughput,
  MFU, measured peak HBM (allocator jitter), compile seconds. A
  regression beyond ``--band`` (default 0.25, budgets file can override)
  fails; noise inside it passes.

Inputs: a bench JSON (the one-line ``{"models": {...}}`` capture) or an
hlo_cost_model JSONL (its ``"record": "summary"`` line). Modes compose:

  # CI perfgate: absolute ceilings/floors from the checked-in budgets
  python tools/perf_diff.py CANDIDATE.json --budgets benchmark/budgets.json

  # A/B: relative diff of two captures
  python tools/perf_diff.py CANDIDATE.json --baseline BASELINE.json

Exit codes: 0 clean, 1 regression(s), 2 unreadable/empty artifact.
"""

import argparse
import json
import os
import sys

# metric -> (direction better, gating kind). Deterministic metrics fail
# on ANY adverse move; timing metrics get the noise band.
METRICS = {
    "fresh_compiles": ("lower", "deterministic"),
    "predicted_peak_bytes": ("lower", "deterministic"),
    "predicted_hbm_bytes": ("lower", "deterministic"),
    "predicted_step_us": ("lower", "deterministic"),
    "flops": ("lower", "deterministic"),
    "peak_hbm_bytes": ("lower", "timing"),
    "step_ms_p50": ("lower", "timing"),
    "step_ms_p95": ("lower", "timing"),
    "compile_seconds_cold": ("lower", "timing"),
    "throughput": ("higher", "timing"),
    "mfu": ("higher", "timing"),
    "mfu_telemetry": ("higher", "timing"),
    # serving SLOs (tools/serve_smoke.py + bench.py serving leg)
    "latency_ms_p50": ("lower", "timing"),
    "latency_ms_p99": ("lower", "timing"),
    "batch_occupancy": ("higher", "timing"),
    # paged decode (bench.py decode leg + tools/decode_smoke.py):
    # throughput carries paged tokens/sec; the A/B ratio and per-token
    # latency gate the raggedness win itself
    "paged_speedup": ("higher", "timing"),
    "token_latency_ms": ("lower", "timing"),
    # cross-request KV reuse (PR 12): shared-vs-unshared best-of-N
    # ratio, prefix-cache effectiveness, and the grouped cross-K/V
    # pool footprint (a pure function of [G, H, T, dh] x layers —
    # deterministic: growth means cross state scales with slots again)
    "bestofn_speedup": ("higher", "timing"),
    "prefix_hit_rate": ("higher", "timing"),
    "cross_kv_bytes": ("lower", "deterministic"),
    # batched beam search over the slot pool (PR 15): rebind-vs-copy
    # reorder tokens/sec ratio (bit-identical n-bests asserted in-leg)
    # and the rebind wave's physically-moved reorder bytes (reorder
    # copies + write-page COW, page-geometry-accounted; deterministic
    # under greedy decode — growth means reorders started copying KV
    # or COW stopped being write-page-only)
    "beam_speedup": ("higher", "timing"),
    "beam_reorder_bytes": ("lower", "deterministic"),
    # speculative decoding (PR 16): draft-then-verify tokens/sec over
    # the sequential FLAGS_speculative=off oracle on the SAME session
    # (bit-identical streams asserted in-leg — the ratio can only come
    # from dispatch amortization), and the drafter's accepted/proposed
    # ratio over the timed wave (deterministic under greedy decode
    # with the leg's seeds, but gated as a timing metric so drafter
    # tuning has headroom — the floor catches lookup regressions)
    "speculative_speedup": ("higher", "timing"),
    "acceptance_rate": ("higher", "timing"),
    # serving resilience (tools/serve_chaos_smoke.py): wall seconds of
    # one synchronous decode snapshot in the restored warm process
    "snapshot_seconds": ("lower", "timing"),
    # router fleet tier (tools/router_smoke.py): end-to-end seconds of
    # one SIGKILL failover (sever detection -> banked snapshot read ->
    # ship -> quiesced restore on the survivor), and the count of
    # client streams the failover LOST (deterministic: the zero-loss
    # contract — any nonzero means a re-driven stream gapped or a
    # banked snapshot stopped covering the in-flight work)
    "migration_seconds": ("lower", "timing"),
    "lost_streams": ("lower", "deterministic"),
    # network front end (tools/frontend_smoke.py + bench.py frontend
    # leg): stream time-to-first-token over a real socket — the
    # latency_ms_* twins above carry the wire unary SLOs
    "ttft_ms": ("lower", "timing"),
    # request tracing (tools/trace_smoke.py): worst per-request span
    # coverage of the CLIENT-observed wall over real sockets — a drop
    # means some serving phase stopped being attributed
    "span_coverage": ("higher", "timing"),
    # step observatory (tools/stepprof_smoke.py + the perf ledger):
    # worst per-step phase coverage of the step wall (a drop means a
    # training phase stopped being attributed), achieved FLOP/s (and,
    # on a chip the peak table knows, MFU) from the cost-model join,
    # input-starvation fraction, and the profiled-leg
    # wall over the off-leg control (the overhead contract)
    "phase_coverage": ("higher", "timing"),
    "achieved_mfu": ("higher", "timing"),
    "achieved_flops_per_sec": ("higher", "timing"),
    "starvation_fraction": ("lower", "timing"),
    "stepprof_overhead": ("lower", "timing"),
}


def _bench_model_metrics(m):
    out = {
        "throughput": m.get("value"),
        "mfu": m.get("mfu"),
        "mfu_telemetry": m.get("mfu_telemetry"),
        "compile_seconds_cold": m.get("compile_seconds_cold"),
        "peak_hbm_bytes": m.get("peak_hbm_bytes"),
        "predicted_peak_bytes": m.get("predicted_peak_bytes"),
    }
    sm = m.get("step_ms") or {}
    out["step_ms_p50"] = sm.get("p50")
    out["step_ms_p95"] = sm.get("p95")
    out["latency_ms_p50"] = m.get("latency_ms_p50")
    out["latency_ms_p99"] = m.get("latency_ms_p99")
    out["batch_occupancy"] = m.get("batch_occupancy")
    out["paged_speedup"] = m.get("paged_speedup")
    out["token_latency_ms"] = m.get("token_latency_ms")
    out["predicted_hbm_bytes"] = m.get("predicted_hbm_bytes")
    out["bestofn_speedup"] = m.get("bestofn_speedup")
    out["prefix_hit_rate"] = m.get("prefix_hit_rate")
    out["cross_kv_bytes"] = m.get("cross_kv_bytes")
    out["beam_speedup"] = m.get("beam_speedup")
    out["beam_reorder_bytes"] = m.get("beam_reorder_bytes")
    out["speculative_speedup"] = m.get("speculative_speedup")
    out["acceptance_rate"] = m.get("acceptance_rate")
    out["snapshot_seconds"] = m.get("snapshot_seconds")
    out["migration_seconds"] = m.get("migration_seconds")
    out["lost_streams"] = m.get("lost_streams")
    out["ttft_ms"] = m.get("ttft_ms")
    out["span_coverage"] = m.get("span_coverage")
    out["phase_coverage"] = m.get("phase_coverage")
    out["achieved_mfu"] = m.get("achieved_mfu")
    out["achieved_flops_per_sec"] = m.get("achieved_flops_per_sec")
    out["starvation_fraction"] = m.get("starvation_fraction")
    out["stepprof_overhead"] = m.get("stepprof_overhead")
    ec = m.get("exec_cache") or {}
    out["fresh_compiles"] = ec.get("fresh_compiles",
                                   m.get("fresh_compiles"))
    return {k: v for k, v in out.items() if v is not None}


def load_artifact(path):
    """-> {model: {metric: value}} from a bench JSON or cost-model JSONL;
    SystemExit(2) with a friendly message when unusable."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        sys.exit("perf_diff: cannot read %s (%s)" % (path, e))
    records = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            pass
    if not records:
        try:
            records = [json.loads(text)]
        except ValueError:
            print("perf_diff: %s is not JSON (or JSONL)" % path)
            raise SystemExit(2)
    models = {}
    for rec in records:
        if not isinstance(rec, dict):
            continue
        if rec.get("record") == "summary":
            # hlo_cost_model JSONL: the analytic roofline — all three
            # numbers are deterministic functions of the traced program
            models[rec.get("model", "cost_model")] = {
                "predicted_step_us": rec.get("step_us_roofline_nameplate"),
                "predicted_hbm_bytes": rec.get("total_hbm_bytes"),
                "flops": rec.get("total_flops"),
            }
        elif isinstance(rec.get("models"), dict):
            for name, m in rec["models"].items():
                if isinstance(m, dict) and "error" not in m:
                    models[name] = _bench_model_metrics(m)
        elif "metric" in rec and "error" not in rec:
            # a bare worker line: one model's record
            models[rec["metric"]] = _bench_model_metrics(rec)
    models = {k: {mk: mv for mk, mv in v.items() if mv is not None}
              for k, v in models.items()}
    models = {k: v for k, v in models.items() if v}
    if not models:
        print("perf_diff: %s parsed but carries no usable model metrics "
              "(bench error capture? telemetry off?)" % path)
        raise SystemExit(2)
    return models


def _gate(metric, cand, limit, band, direction, kind, source):
    """One comparison -> (ok, effective_limit). ``limit`` is the
    baseline value or the budget ceiling/floor; timings stretch it by
    the band, deterministic metrics don't."""
    eff = float(limit)
    if kind == "timing":
        eff = eff * (1.0 + band) if direction == "lower" else \
            eff * (1.0 - band)
    ok = (cand <= eff) if direction == "lower" else (cand >= eff)
    return ok, eff


def compare(candidate, reference, band, source, results,
            require_all=False):
    """Gate every shared (model, metric) pair; append result rows.

    ``require_all`` (budget mode): a budgeted (model, metric) pair the
    candidate doesn't carry is itself a FAILURE — otherwise a PR that
    breaks the telemetry capture (metrics vanish from the artifact)
    silently weakens the gate while 'perf_diff: clean' still prints."""
    for model, cand_metrics in sorted(candidate.items()):
        ref_metrics = reference.get(model)
        if not ref_metrics:
            continue
        for metric, cand in sorted(cand_metrics.items()):
            spec = METRICS.get(metric)
            if spec is None or metric not in ref_metrics:
                continue
            direction, kind = spec
            ref = ref_metrics[metric]
            ok, eff = _gate(metric, float(cand), float(ref), band,
                            direction, kind, source)
            results.append({
                "model": model, "metric": metric, "kind": kind,
                "candidate": cand, "reference": ref,
                "effective_limit": round(eff, 6), "source": source,
                "ok": ok,
            })
    if not require_all:
        return
    for model, ref_metrics in sorted(reference.items()):
        cand_metrics = candidate.get(model)
        for metric in sorted(ref_metrics):
            if metric not in METRICS:
                continue
            if cand_metrics is None or metric not in cand_metrics:
                results.append({
                    "model": model, "metric": metric, "kind": "missing",
                    "candidate": None,
                    "reference": ref_metrics[metric],
                    "effective_limit": None, "source": source,
                    "ok": False,
                })


def budget_reference(budgets):
    """Flatten the budgets file to {model: {metric: limit}} (+ its band).
    Entries are ``{"max"|"min": value, "why": lineage}`` — the why
    strings are the audit trail for every number."""
    ref = {}
    for model, entries in (budgets.get("models") or {}).items():
        ref[model] = {}
        for metric, spec in entries.items():
            if not isinstance(spec, dict):
                ref[model][metric] = spec
                continue
            limit = spec.get("max", spec.get("min"))
            if limit is not None:
                ref[model][metric] = limit
    return ref, float(budgets.get("band", 0.25))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="diff bench/cost-model artifacts; gate on budgets")
    ap.add_argument("candidate", help="bench JSON or cost-model JSONL")
    ap.add_argument("--baseline", default=None,
                    help="reference artifact for a relative diff")
    ap.add_argument("--budgets", default=None,
                    help="benchmark/budgets.json absolute gate")
    ap.add_argument("--band", type=float, default=0.25,
                    help="noise band for timing metrics (relative mode; "
                         "the budgets file carries its own)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full result table as one JSON line")
    ap.add_argument("--models", default=None,
                    help="comma list: gate only these models (a partial "
                         "capture — e.g. the serve smoke's — isn't "
                         "failed for the models it never measured)")
    args = ap.parse_args(argv)

    if not args.baseline and not args.budgets:
        default_budgets = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "budgets.json")
        if os.path.exists(default_budgets):
            args.budgets = default_budgets
        else:
            ap.error("need --baseline and/or --budgets")

    candidate = load_artifact(args.candidate)
    only = None
    if args.models:
        only = {m.strip() for m in args.models.split(",") if m.strip()}
        candidate = {k: v for k, v in candidate.items() if k in only}
        if not candidate:
            print("perf_diff: candidate carries none of --models %s"
                  % sorted(only))
            raise SystemExit(2)
    results = []
    if args.baseline:
        baseline = load_artifact(args.baseline)
        if only is not None:
            baseline = {k: v for k, v in baseline.items() if k in only}
        compare(candidate, baseline, args.band, "baseline", results)
    if args.budgets:
        try:
            with open(args.budgets) as f:
                budgets = json.load(f)
        except (OSError, ValueError) as e:
            print("perf_diff: cannot read budgets %s (%s)"
                  % (args.budgets, e))
            raise SystemExit(2)
        ref, band = budget_reference(budgets)
        if only is not None:
            ref = {k: v for k, v in ref.items() if k in only}
        compare(candidate, ref, band, "budget", results,
                require_all=True)

    if not results:
        print("perf_diff: no overlapping (model, metric) pairs to gate — "
              "nothing compared, nothing proven")
        raise SystemExit(2)

    failures = [r for r in results if not r["ok"]]
    for r in results:
        mark = "FAIL" if not r["ok"] else "ok  "
        print("%s %-12s %-22s %-13s cand=%-14s %s=%-14s limit=%s"
              % (mark, r["model"], r["metric"], r["kind"],
                 r["candidate"], r["source"], r["reference"],
                 r["effective_limit"]))
    if args.json:
        print(json.dumps({"results": results,
                          "failures": len(failures)}, sort_keys=True))
    if failures:
        print("perf_diff: %d regression(s) — deterministic counters gate "
              "hard, timings beyond the noise band" % len(failures))
        raise SystemExit(1)
    print("perf_diff: clean (%d checks)" % len(results))


if __name__ == "__main__":
    main()
