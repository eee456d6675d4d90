"""Training-step decomposition: phase timings + per-HLO-op xprof shares.

The measurement behind the MFU push: where does the step time go?

Two independent views, printed as JSON lines:

1. Phase timing — the model's program is compiled and timed three ways
   (forward only; forward+backward via append_backward; the full train
   step with the optimizer), so bwd and optimizer cost are the deltas.
2. ``--from-jsonl PATH`` — skip the model runs entirely and summarize an
   EXISTING telemetry snapshot (the ``<FLAGS_metrics_path>.steps.jsonl``
   a training/serving process left behind); ``--per-device`` adds the
   per-device view over the labeled step records (dispatch->ready time
   per device and the straggler ratio) that the multichip telemetry
   writes into each record; ``--memory`` adds the HBM view — per-step
   peak watermark trajectory, predicted-vs-measured peak, and the top
   ledger holders (observability/memory.py writes all three into the
   records). ``--requests PATH`` is the serving twin: the per-REQUEST
   view over a request-trace snapshot (the
   ``<FLAGS_metrics_path>.traces.jsonl`` a FLAGS_request_tracing=1
   serving process left behind) — fleet TTFT / queue / prefill /
   decode split plus the top-N slowest requests by trace id
   (``tools/trace_view.py`` renders any one of them as a waterfall).
   ``--steps PATH`` (a ``.stepprof.jsonl`` from FLAGS_step_profile=1)
   is the training twin: per-step phase split (input wait / feed /
   compile / dispatch / device / fetch / host), achieved-MFU
   percentiles, starvation fraction, and the top-N slowest steps with
   per-phase attribution and regression flags.
3. ``--xprof`` — run the full step under ``jax.profiler.trace`` and
   aggregate XLA op self-times from the xplane.pb the profiler writes.
   The xplane wire format is decoded directly (a ~60-line generic
   protobuf reader; the tensorboard_plugin_profile converter in this
   image is incompatible with its tensorflow build, and the schema —
   XPlane{name=2, lines=3, event_metadata=4} / XLine{name=2, events=4} /
   XEvent{metadata_id=1, duration_ps=3} — is stable across xprof
   versions). Top-N ops by total self time, with % of the plane.

Usage (CPU smoke / TPU real):
  BENCH_PLATFORM=cpu python tools/step_breakdown.py --model resnet50 --xprof
  python tools/step_breakdown.py --model resnet50 --steps 20 --xprof
"""

import argparse
import glob
import json
import os
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# xplane.pb decoding (generic protobuf wire reader; schema constants above)
# ---------------------------------------------------------------------------


def _varint(buf, i):
    v = s = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << s
        if not b & 0x80:
            return v, i
        s += 7


def _fields(buf):
    i = 0
    out = []
    while i < len(buf):
        tag, i = _varint(buf, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        else:
            raise ValueError("unsupported wire type %d" % wt)
        out.append((fn, wt, v))
    return out


def op_times_from_xplane(path, plane_filter=None):
    """{plane_name: {line_name: {op_name: total_self_time_ps}}} from one
    xplane.pb. Aggregation is PER LINE: a TPU device plane carries several
    XLines ("Steps", "XLA Modules", "XLA Ops", ...) whose events nest —
    summing across lines multiply-counts the same wall time and, worse,
    drowns the HLO op names in step-number events (the round-3 capture's
    "op 54: 90.7%" artifact, VERDICT r3 Weak #4)."""
    data = open(path, "rb").read()
    result = {}
    for fn, wt, plane_buf in _fields(data):
        if fn != 1 or wt != 2:  # XSpace.planes
            continue
        plane = _fields(plane_buf)
        name = next((v.decode("utf-8", "replace")
                     for f, w, v in plane if f == 2 and w == 2), "")
        if plane_filter and plane_filter not in name:
            continue
        # event metadata id -> name (map entries: key=1, value=XEventMetadata)
        md = {}
        for f, w, v in plane:
            if f != 4 or w != 2:
                continue
            entry = _fields(v)
            key = next((x for fk, _, x in entry if fk == 1), None)
            val = next((x for fk, wk, x in entry if fk == 2 and wk == 2), b"")
            try:
                emeta = _fields(val)
                ename = next((x.decode("utf-8", "replace")
                              for fk, wk, x in emeta if fk == 2 and wk == 2),
                             "")
            except (ValueError, IndexError):
                ename = ""
            if key is not None and ename:
                md[key] = ename
        # lines (XPlane.lines=3) -> events (XLine.events=4), keyed by the
        # line's name (XLine.name=2)
        lines = {}
        for f, w, v in plane:
            if f != 3 or w != 2:
                continue
            lfields = _fields(v)
            lname = next((x.decode("utf-8", "replace")
                          for lf, lw, x in lfields if lf == 2 and lw == 2),
                         "")
            times = lines.setdefault(lname or "line", defaultdict(int))
            for lf, lw, lv in lfields:
                if lf != 4 or lw != 2:
                    continue
                ev = _fields(lv)
                mid = next((x for fk, _, x in ev if fk == 1), None)
                dur = next((x for fk, _, x in ev if fk == 3), 0)
                if mid is not None:
                    times[md.get(mid, "id:%s" % mid)] += dur
        lines = {ln: dict(t) for ln, t in lines.items() if t}
        if lines:
            result[name] = lines
    return result


# ---------------------------------------------------------------------------
# phase timing
# ---------------------------------------------------------------------------


def _build(fluid, model, on_tpu, mode):
    """mode: 'fwd' | 'fwdbwd' | 'step'. Returns (main, startup, loss)."""
    from paddle_tpu.models import resnet, transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup):
        if model == "resnet50":
            img, bs = (224, 128) if on_tpu else (64, 8)
            pixel, label = fluid.layers.random_data_generator(
                shapes=[[bs, 3, img, img], [bs, 1]],
                dtypes=["float32", "int64"], int_high=999)
            pred = resnet.resnet_imagenet(pixel, 1000, depth=50)
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(input=pred, label=label))
            denom = bs
        else:
            seq, bs = (256, 64) if on_tpu else (32, 4)
            nl, nh, dm, di = (6, 8, 512, 2048) if on_tpu else (2, 4, 64, 128)
            vocab = 32000 if on_tpu else 500
            loss, feeds, _ = transformer.build(
                src_vocab_size=vocab, trg_vocab_size=vocab, max_length=seq,
                n_layer=nl, n_head=nh, d_model=dm, d_inner=di, dropout=0.1)
            denom = bs * seq
        if mode == "fwdbwd":
            # lr=0 SGD anchors the backward as live program state; a bare
            # append_backward would leave grads unread and XLA would DCE
            # the whole backward (measured: "bwd" came out free)
            fluid.optimizer.SGD(learning_rate=0.0).minimize(loss)
        elif mode == "step":
            fluid.optimizer.Momentum(
                learning_rate=0.1, momentum=0.9).minimize(loss)
    return main, startup, loss, denom


def _transformer_feed(on_tpu):
    import numpy as np

    seq, bs = (256, 64) if on_tpu else (32, 4)
    vocab = 32000 if on_tpu else 500
    rng = np.random.RandomState(11)
    return {
        "src_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "src_len": np.full((bs, 1), seq, "int64"),
        "trg_word": rng.randint(1, vocab, (bs, seq)).astype("int64"),
        "trg_len": np.full((bs, 1), seq, "int64"),
        "label": rng.randint(1, vocab, (bs, seq)).astype("int64"),
    }


def _time_phase(fluid, model, on_tpu, mode, steps, warmup, use_amp):
    """Phase timing via the step-telemetry JSONL snapshot: the executors
    already record per-step wall time (observability/telemetry.py), so
    this tool stopped carrying its own perf_counter loop — it runs the
    steps, dumps the snapshot, and averages the records. One instrument,
    one truth; the same numbers land in the Prometheus scrape."""
    import numpy as np
    from paddle_tpu.observability import telemetry
    from paddle_tpu.transpiler import rewrite_program_amp
    from paddle_tpu import unique_name

    unique_name.switch()
    main, startup, loss, denom = _build(fluid, model, on_tpu, mode)
    if use_amp:
        rewrite_program_amp(main, "bfloat16")
    feed = _transformer_feed(on_tpu) if model == "transformer" else {}
    telemetry.enable(True)
    with fluid.scope_guard(fluid.executor.Scope()):
        exe = fluid.Executor(fluid.TPUPlace() if on_tpu
                             else fluid.CPUPlace())
        exe.run(startup)
        for _ in range(warmup):
            exe.run(main, feed=feed, fetch_list=[])
        exe.run(main, feed=feed, fetch_list=[loss])
        telemetry.reset()  # timed window starts here
        for _ in range(steps - 1):
            exe.run(main, feed=feed, fetch_list=[])
        out = exe.run(main, feed=feed, fetch_list=[loss])
        with tempfile.TemporaryDirectory(prefix="step_tel_") as d:
            snap = os.path.join(d, "steps.jsonl")
            n = telemetry.write_steps_jsonl(snap)
            with open(snap) as f:
                recs = [json.loads(line) for line in f if line.strip()]
        telemetry.reset()
    assert np.isfinite(float(np.ravel(np.asarray(out[0]))[0]))
    if len(recs) != steps or n != steps:
        # friendly, actionable — not a bare AssertionError traceback
        sys.exit(
            "step_breakdown: telemetry recorded %d step(s) for %d timed "
            "steps — something disabled telemetry mid-run (check that "
            "nothing calls telemetry.enable(False) or reset() while the "
            "phase loop runs)" % (len(recs), steps))
    dt = sum(r["wall_s"] for r in recs) / sum(r["steps"] for r in recs)
    return dt, denom


# ---------------------------------------------------------------------------
# offline view over an existing telemetry snapshot
# ---------------------------------------------------------------------------


def _load_steps_jsonl(path):
    """Records from a telemetry steps JSONL, or a friendly exit — a
    missing/empty snapshot is an operator mistake (telemetry was off or
    the path is wrong), not a crash."""
    if not os.path.exists(path):
        sys.exit(
            "step_breakdown: %s does not exist.\nRun the workload with "
            "FLAGS_telemetry=1 and FLAGS_metrics_path=<p> (the snapshot "
            "lands at <p>.steps.jsonl), or pass that .steps.jsonl path "
            "here." % path)
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
            "step_breakdown: %s is empty — the process wrote no step "
            "records (was FLAGS_telemetry=1? did any step complete?)"
            % path)
    return recs


def _load_traces_jsonl(path):
    """Records from a request-trace JSONL, or a friendly exit — same
    contract as ``_load_steps_jsonl``: a missing/empty snapshot means
    tracing was off or the path is wrong, not a crash."""
    if not os.path.exists(path):
        sys.exit(
            "step_breakdown: %s does not exist.\nRun the serving "
            "workload with FLAGS_request_tracing=1, FLAGS_telemetry=1 "
            "and FLAGS_metrics_path=<p> (completed traces land at "
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
            "step_breakdown: %s is empty — the process completed no "
            "traced request (was FLAGS_request_tracing=1? did any "
            "request finish before the telemetry flush?)" % path)
    return recs


def _load_stepprof_jsonl(path):
    """Records from a step-profile JSONL, or a friendly exit — same
    contract as the other loaders: a missing/empty snapshot means the
    observatory was off or the path is wrong, not a crash."""
    if not os.path.exists(path):
        sys.exit(
            "step_breakdown: %s does not exist.\nRun the training "
            "workload with FLAGS_step_profile=1, FLAGS_telemetry=1 and "
            "FLAGS_metrics_path=<p> (profiled steps land at "
            "<p>.stepprof.jsonl), or pass that .stepprof.jsonl path "
            "here." % path)
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
            "step_breakdown: %s is empty — the process profiled no step "
            "(was FLAGS_step_profile=1? did any executor step complete?)"
            % path)
    return recs


# phase axis of the step observatory's records (step_profiler.PHASES)
_STEPPROF_PHASES = ("input_wait", "feed", "compile", "dispatch", "device",
                    "fetch", "host")


def _summarize_stepprof(recs, top=5):
    """The per-step training view over a step-profile snapshot: where
    did each step's wall go (phase split), achieved-MFU percentiles,
    starvation fraction, and the top-N slowest steps with per-phase
    attribution and regression flags — the training twin of
    ``--requests``."""
    timed = [r for r in recs if not r.get("dispatch_only")]
    per_step = [r["step_s"] for r in timed]
    total_wall = sum(r.get("wall_s", 0.0) for r in timed)
    phase_totals = {p: 0.0 for p in _STEPPROF_PHASES}
    for r in timed:
        for p, v in (r.get("phases") or {}).items():
            phase_totals[p] = phase_totals.get(p, 0.0) + v
    total_input = phase_totals.get("input_wait", 0.0)
    total_attr = total_wall + total_input  # wall excludes pre-step waits
    mfus = [r["achieved_mfu"] for r in timed
            if r.get("achieved_mfu") is not None]
    bounds = {}
    for r in timed:
        b = r.get("bound", "unknown")
        bounds[b] = bounds.get(b, 0) + 1
    regressions = [r for r in timed if r.get("regression")]

    def ms(v, nd=3):
        return round(v * 1e3, nd) if v is not None else None

    print(json.dumps({
        "step_records": len(recs),
        "steps": sum(int(r.get("steps", 1)) for r in timed),
        "origins": sorted({r.get("origin") for r in timed}),
        "step_ms": {"p50": ms(_percentile(per_step, 50)),
                    "p95": ms(_percentile(per_step, 95)),
                    "p99": ms(_percentile(per_step, 99))},
        "phase_split": {
            p: round(phase_totals.get(p, 0.0) / total_attr, 4)
            for p in _STEPPROF_PHASES if total_attr > 0},
        "coverage_min": (round(min(r.get("coverage", 0.0)
                                   for r in timed), 4)
                         if timed else None),
        "starvation_fraction": (round(total_input / total_attr, 4)
                                if total_attr > 0 else None),
        "achieved_mfu": {
            "p50": (round(_percentile(mfus, 50), 6) if mfus else None),
            "p95": (round(_percentile(mfus, 95), 6) if mfus else None),
        },
        "bound": bounds,
        "regressions": len(regressions),
    }))
    slowest = sorted(timed, key=lambda r: -r.get("step_s", 0.0))
    for r in slowest[:max(0, int(top))]:
        reg = r.get("regression")
        print(json.dumps({
            "slow_step": r.get("fingerprint", "")[:16] or r.get("origin"),
            "origin": r.get("origin"),
            "steps": r.get("steps", 1),
            "step_ms": ms(r.get("step_s")),
            "phases_ms": {p: ms(v) for p, v in
                          (r.get("phases") or {}).items()},
            "coverage": round(r.get("coverage", 0.0), 4),
            "achieved_mfu": r.get("achieved_mfu"),
            "predicted_ratio": r.get("predicted_ratio"),
            "bound": r.get("bound"),
            "regression": ({"kind": reg["kind"], "phase": reg["phase"]}
                           if reg else None),
        }))


def _summarize_requests(recs, top=5):
    """The per-request serving view over a trace snapshot: where did
    each request's wall time go (queue wait / prefill / decode /
    wire flush), fleet TTFT and inter-token percentiles, and the top-N
    slowest requests — the offline twin of the live ``trace`` wire
    endpoint."""
    stats = [r.get("stats") or {} for r in recs]

    def col(key):
        return [s[key] for s in stats if s.get(key) is not None]

    def ms(v, nd=3):
        return round(v * 1e3, nd) if v is not None else None

    outcomes = {}
    for r in recs:
        o = r.get("outcome", "ok")
        outcomes[o] = outcomes.get(o, 0) + 1
    print(json.dumps({
        "requests": len(recs),
        "outcomes": outcomes,
        "ttft_ms": {"p50": ms(_percentile(col("ttft_s"), 50)),
                    "p95": ms(_percentile(col("ttft_s"), 95))},
        "wall_ms": {"p50": ms(_percentile(col("wall_s"), 50)),
                    "p95": ms(_percentile(col("wall_s"), 95))},
        "split_ms_p50": {
            "queue": ms(_percentile(col("queue_s"), 50)),
            "prefill": ms(_percentile(col("prefill_s"), 50)),
            "decode": ms(_percentile(col("decode_s"), 50)),
            "flush": ms(_percentile(col("flush_s"), 50)),
        },
        "intertoken_ms": {
            "p50": round(_percentile(col("intertoken_p50_ms"), 50)
                         or 0, 3),
            "p95": round(_percentile(col("intertoken_p95_ms"), 95)
                         or 0, 3),
        },
        "tokens": sum(int(s.get("tokens", 0)) for s in stats),
        "tokens_from_spec": sum(int(s.get("tokens_from_spec", 0))
                                for s in stats),
        "page_seconds": round(sum(s.get("page_seconds", 0.0)
                                  for s in stats), 4),
        "span_coverage_min": (round(min(col("span_coverage")), 4)
                              if col("span_coverage") else None),
    }))
    slowest = sorted(recs, key=lambda r: -(r.get("stats") or {})
                     .get("wall_s", 0.0))[:max(0, int(top))]
    for r in slowest:
        s = r.get("stats") or {}
        print(json.dumps({
            "slow_request": r.get("trace_id"),
            "endpoint": r.get("endpoint"),
            "outcome": r.get("outcome"),
            "wall_ms": ms(s.get("wall_s")),
            "ttft_ms": ms(s.get("ttft_s")),
            "queue_ms": ms(s.get("queue_s")),
            "prefill_ms": ms(s.get("prefill_s")),
            "decode_ms": ms(s.get("decode_s")),
            "flush_ms": ms(s.get("flush_s")),
            "tokens": s.get("tokens"),
            "spec_fraction": s.get("spec_fraction"),
            "cow_copies": s.get("cow_copies"),
        }))


def _percentile(vals, q):
    if not vals:
        return None
    vals = sorted(vals)
    import math

    k = max(0, min(len(vals) - 1,
                   int(math.ceil(q / 100.0 * len(vals))) - 1))
    return vals[k]


def _summarize_memory(recs):
    """The HBM view over a telemetry snapshot: watermark trajectory,
    predicted-vs-measured, top holders — same friendly degradation as
    --per-device when the records carry no memory fields."""
    with_mem = [r for r in recs if r.get("peak_hbm_bytes")]
    if not with_mem:
        print(json.dumps({
            "memory": None,
            "note": "no record carries peak_hbm_bytes — the snapshot "
                    "predates the memory ledger or telemetry ran "
                    "without any executor step (the ledger is written "
                    "by Executor/ParallelExecutor runs)"}))
        return
    peaks = [r["peak_hbm_bytes"] for r in with_mem]
    preds = [r["predicted_peak_bytes"] for r in with_mem
             if r.get("predicted_peak_bytes")]
    last = with_mem[-1]
    out = {
        "records_with_memory": len(with_mem),
        "peak_hbm_mb": {
            "max": round(max(peaks) / 1e6, 3),
            "p95": round((_percentile(peaks, 95) or 0) / 1e6, 3),
            "last": round(peaks[-1] / 1e6, 3),
        },
        "predicted_peak_mb": (round(max(preds) / 1e6, 3) if preds
                              else None),
        "predicted_over_measured": (round(max(preds) / max(peaks), 3)
                                    if preds and max(peaks) else None),
        "top_holders": [
            {"name": n, "kind": k, "mb": round(b / 1e6, 3)}
            for n, k, b in (last.get("hbm_top") or [])],
    }
    print(json.dumps(out))


def _summarize_jsonl(recs, per_device=False, memory=False):
    timed = [r for r in recs if not r.get("dispatch_only")]
    per_step = [r["step_s"] for r in timed]
    print(json.dumps({
        "records": len(recs),
        "steps": sum(r.get("steps", 1) for r in recs),
        "executors": sorted({r.get("executor") for r in recs}),
        "p50_ms": round((_percentile(per_step, 50) or 0) * 1e3, 3),
        "p95_ms": round((_percentile(per_step, 95) or 0) * 1e3, 3),
        "p99_ms": round((_percentile(per_step, 99) or 0) * 1e3, 3),
        "feed_mb": round(sum(r.get("feed_bytes", 0)
                             for r in recs) / 1e6, 3),
        "fetch_mb": round(sum(r.get("fetch_bytes", 0)
                              for r in recs) / 1e6, 3),
    }))
    if memory:
        _summarize_memory(recs)
    if not per_device:
        return
    with_dev = [r for r in recs if r.get("device_times")]
    if not with_dev:
        print(json.dumps({
            "per_device": None,
            "note": "no record carries device_times — the snapshot came "
                    "from a single-device executor (per-device step "
                    "times are recorded by ParallelExecutor runs)"}))
        return
    agg = defaultdict(list)
    for r in with_dev:
        for dev, t in r["device_times"].items():
            agg[dev].append(t)
    rows = {
        dev: {"steps": len(ts),
              "mean_ms": round(sum(ts) / len(ts) * 1e3, 3),
              "max_ms": round(max(ts) * 1e3, 3)}
        for dev, ts in sorted(agg.items())
    }
    worst = [max(r["device_times"], key=r["device_times"].get)
             for r in with_dev]
    straggler = max(set(worst), key=worst.count)
    means = sorted(v["mean_ms"] for v in rows.values())
    mid = len(means) // 2
    med = means[mid] if len(means) % 2 else (
        means[mid - 1] + means[mid]) / 2.0
    print(json.dumps({
        "per_device": rows,
        "most_frequent_straggler": straggler,
        "imbalance_max_over_median": round(
            max(means) / med, 4) if med else None,
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "transformer"])
    ap.add_argument("--steps", default="10", metavar="N|PATH",
                    help="model-run mode: number of timed steps. With a "
                         "PATH to a step-profile JSONL "
                         "(<FLAGS_metrics_path>.stepprof.jsonl): offline "
                         "training view — phase split, achieved-MFU "
                         "percentiles, starvation fraction, top-N "
                         "slowest steps with regression flags")
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--xprof", action="store_true",
                    help="also capture + aggregate an xprof trace")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--from-jsonl", metavar="PATH", default=None,
                    help="summarize an existing telemetry steps JSONL "
                         "instead of running the model")
    ap.add_argument("--per-device", action="store_true",
                    help="with --from-jsonl: per-device step-time table "
                         "over the labeled step records")
    ap.add_argument("--memory", action="store_true",
                    help="with --from-jsonl: peak-HBM trajectory, "
                         "predicted-vs-measured peak, top ledger holders")
    ap.add_argument("--requests", metavar="PATH", default=None,
                    help="summarize a request-trace JSONL "
                         "(<FLAGS_metrics_path>.traces.jsonl): fleet "
                         "TTFT/queue/prefill/decode split + top-N "
                         "slowest requests")
    args = ap.parse_args()

    try:
        args.steps = int(args.steps)
    except ValueError:
        # --steps <path.stepprof.jsonl>: the offline training view,
        # symmetric to --requests
        _summarize_stepprof(_load_stepprof_jsonl(args.steps),
                            top=args.top)
        return

    if args.requests:
        _summarize_requests(_load_traces_jsonl(args.requests),
                            top=args.top)
        return
    if args.from_jsonl:
        _summarize_jsonl(_load_steps_jsonl(args.from_jsonl),
                         per_device=args.per_device, memory=args.memory)
        return
    if args.memory:
        sys.exit(
            "step_breakdown: --memory reads a telemetry snapshot — pass "
            "--from-jsonl <p>.steps.jsonl (run the workload with "
            "FLAGS_telemetry=1 and FLAGS_metrics_path=<p> to produce one)")

    import jax

    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    import paddle_tpu as fluid

    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    use_amp = on_tpu

    phases = {}
    for mode in ("fwd", "fwdbwd", "step"):
        dt, denom = _time_phase(fluid, args.model, on_tpu, mode,
                                args.steps, args.warmup, use_amp)
        phases[mode] = dt
        print(json.dumps({"phase": mode, "ms": round(dt * 1e3, 3),
                          "per_unit_us": round(dt / denom * 1e6, 3)}))
    print(json.dumps({
        "phase": "deltas",
        "bwd_ms": round((phases["fwdbwd"] - phases["fwd"]) * 1e3, 3),
        "opt_ms": round((phases["step"] - phases["fwdbwd"]) * 1e3, 3),
        "bwd_over_fwd": round(phases["fwdbwd"] / phases["fwd"] - 1, 2),
    }))

    if not args.xprof:
        return
    from paddle_tpu.transpiler import rewrite_program_amp
    from paddle_tpu import unique_name

    unique_name.switch()
    main_p, startup, loss, _ = _build(fluid, args.model, on_tpu, "step")
    if use_amp:
        rewrite_program_amp(main_p, "bfloat16")
    feed = _transformer_feed(on_tpu) if args.model == "transformer" else {}
    with fluid.scope_guard(fluid.executor.Scope()):
        exe = fluid.Executor(fluid.TPUPlace() if on_tpu
                             else fluid.CPUPlace())
        exe.run(startup)
        for _ in range(args.warmup):
            exe.run(main_p, feed=feed, fetch_list=[])
        trace_dir = tempfile.mkdtemp(prefix="step_breakdown_")
        with jax.profiler.trace(trace_dir):
            for _ in range(args.steps):
                exe.run(main_p, feed=feed, fetch_list=[])
            exe.run(main_p, feed=feed, fetch_list=[loss])
    # device plane if present (TPU), else the host CPU plane; within a
    # plane prefer the "XLA Ops" line — that's where the per-HLO self
    # times live (the "Steps"/"XLA Modules" lines carry whole-step and
    # whole-module envelopes that would drown the op table)
    for path in glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True):
        planes = op_times_from_xplane(path)
        device = {n: t for n, t in planes.items() if "CPU" not in n} or planes
        for pname, lines in sorted(device.items()):
            preferred = [ln for ln in lines if "XLA Ops" in ln] or \
                sorted(lines)
            for lname in preferred:
                times = lines[lname]
                total = sum(times.values())
                if not total:
                    continue
                top = sorted(times.items(), key=lambda kv: -kv[1])[:args.top]
                print(json.dumps({
                    "plane": pname, "line": lname,
                    "total_ms": round(total / 1e9, 3),
                    "top_ops": [
                        {"op": op, "ms": round(t / 1e9, 3),
                         "pct": round(100.0 * t / total, 1)}
                        for op, t in top
                    ]}))


if __name__ == "__main__":
    main()
