"""Hold an open- or closed-loop serving cell under several windows in ONE
process, behind one warm server. Two uses:

* find the knee of an open loop, the highest rate the system sustains.
  Made once, when the cell is defined (and again by a later benchmark PR
  after an optimisation moved it); the cell then offers a FIXED share of
  the rate found, written into its traffic file as a number::

    python perfbench/sweep.py --workload serve_base_steady --rates 20,30,40,50,60,70 --hold 20 --seed 5

  A rate is sustained when no request failed and the backlog did not grow:
  the median time to first token of the window's last third is no more
  than 1.5 x that of its first third plus 50 ms.

* read how far the cell's own traffic spreads from seed to seed, without
  paying the set-up for every window (the driver's check pays it: each of
  its runs is a process of ``run.py``)::

    python perfbench/sweep.py --workload serve_base_steady,serve_base_saturated --seeds 11,12,13,14,15,16 --sets 2 --hold 51

  (cells of one configuration share the server, each with its own traffic.)
  Each set's spread is (q3 - q1) / median by ``statistics.quantiles``.
"""

import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness, serve_common  # noqa: E402
from perfbench.loadgen import percentile  # noqa: E402


def sustained(records, hold):
    ok = [r for r in records if 0 <= r["due"] < hold and not r["failed"]]
    first = [r["first"] - r["due"] for r in ok if r["due"] < hold / 3.0]
    last = [r["first"] - r["due"] for r in ok if r["due"] >= 2 * hold / 3.0]
    if not first or not last:
        return False, None, None
    a, b = percentile(first, 50), percentile(last, 50)
    return b <= 1.5 * a + 0.05, a, b


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--hold", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    # several cells of ONE configuration share the server: a,b
    cells = [harness.Cell(w) for w in args.workload.split(",")]
    cell = cells[0]
    if any(c.config != cell.config for c in cells):
        raise SystemExit("the cells named do not share a configuration")
    if args.rates:
        windows = [(cell, dict(cell.traffic, rate_rps=float(r)),
                    args.seed + k)
                   for k, r in enumerate(args.rates.split(","))]
    else:
        windows = [(c, c.traffic, int(s)) for c in cells
                   for _set in range(args.sets)
                   for s in args.seeds.split(",")]

    import paddle_tpu as fluid
    from paddle_tpu.core import exec_cache

    fluid.require_accelerator(cell.chips)
    exec_cache.enable_xla_cache()
    out_dir = os.path.join(cell.root, "perfbench_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    server = serve_common.Server(cell, args.seed, fluid.TPUPlace(),
                                 harness.Setup(time.perf_counter()))
    rows = []
    try:
        server.warm()
        gc.collect()  # as run.py ends its set-up
        server.instrument()
        server.start(cell.traffic.get("max_stream_backlog", 4096))
        for cell, traffic, seed in windows:
            watch = harness.GcWatch()
            watch.start()
            t_open = []
            summary, records, _host = serve_common.drive(
                server, traffic, args.hold,
                serve_common.transformer_client(cell, traffic, seed,
                                                args.hold, out_dir),
                on_open=lambda t: t_open.append(
                    time.perf_counter() - (time.time() - t)))
            stalls = watch.stop(t_open[0], args.hold)
            keeps, a, b = sustained(records, args.hold)
            row = {"cell": cell.name, "rate_rps": traffic.get("rate_rps"),
                   "seed": seed,
                   "attempted": summary["attempted"],
                   "failed": summary["failed"],
                   "completed_rps": (summary["attempted"]
                                     - summary["failed"]) / args.hold,
                   "tokens_per_s": summary["tokens_per_s"],
                   "ttft_p50_ms": percentile(summary["ttft_ms"], 50),
                   "ttft_p95_ms": percentile(summary["ttft_ms"], 95),
                   "tpot_p95_ms": percentile(summary["tpot_ms"], 95),
                   "late_p99_ms": percentile(summary["late_ms"], 99),
                   "ttft_p50_first_third_s": a, "ttft_p50_last_third_s": b,
                   "pending_at_end": len(server.session.pending_requests),
                   "full_collections": stalls,
                   "pool_conserved": None,
                   "sustained": bool(keeps and summary["failed"] == 0)}
            deadline = time.time() + 60
            while server.session.active_slots and time.time() < deadline:
                time.sleep(0.2)
            row["pool_conserved"] = bool(server.session.pool_conserved)
            rows.append(row)
            harness.log(json.dumps(row))
    finally:
        server.close()
    if args.rates:
        good = [r["rate_rps"] for r in rows if r["sustained"]]
        harness.log("knee: the highest sustained rate swept is %s "
                    "requests/s" % (max(good) if good else None))
        return 0
    n = len(args.seeds.split(","))
    for k in range(len(rows) // n):
        one = rows[k * n:(k + 1) * n]
        for key in ("ttft_p95_ms", "tpot_p95_ms", "tokens_per_s"):
            vals = [r[key] for r in one]
            if n >= 2 and all(v is not None for v in vals):
                harness.log("%s set %d, %s: median %.4f, spread %.4f%% over"
                            " %d windows" % (one[0]["cell"], k % args.sets
                                             + 1, key,
                                             statistics.median(vals),
                                             100 * spread(vals), n))
    return 0


if __name__ == "__main__":
    sys.exit(main())
