"""Hold a decoder-only family's serving cell under several windows in ONE
process, behind one warm server: how far the cell's own traffic spreads
from seed to seed, without paying the set-up for every window (the driver's
check pays it: each of its runs is a process of ``run.py``)::

    python perfbench/sweep_decoder.py --workload serve_glm52_longctx --seeds 11,12,13,14,15,16 --sets 2 --hold 51

``sweep.py``'s second use for the cells whose entry hands ``decoder_family``
a common module (``entries/<entry>.py``'s ``common``: its ``Server`` and
``client_sizes``); ``sweep.py`` itself builds the Transformer's server. The
server is built, warmed, instrumented and started as ``decoder_family.run``
does it, each window has a load generator process of its own, and the next
window opens when the last one's streams have drained. Each set's spread is
(q3 - q1) / median by ``statistics.quantiles``. On ``serve_glm52_longctx`` a
whole run of ``run.py`` on a window's seed read what the window read
(PERF.md section 6, PR 39); the driver's check makes whole runs, and they
decide.
"""

import argparse
import gc
import importlib
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
from perfbench.sweep import spread  # noqa: E402


def hold_windows(cell, common, seeds, sets, hold, weights_seed, place,
                 pool=None):
    """A row a window: ``sets`` times the ``seeds`` in turn. ``pool``
    overrides keys of the configuration's pool (a what-if, never the
    cell)."""
    if pool:
        cell.config["pool"].update(pool)
    out_dir = os.path.join(cell.root, "perfbench_out", "sweep_decoder")
    os.makedirs(out_dir, exist_ok=True)
    traffic = cell.traffic
    server = common.Server(cell, weights_seed, place,
                           harness.Setup(time.perf_counter()))
    vocab, longest = common.client_sizes(cell.config)
    rows = []
    try:
        server.warm()
        gc.collect()  # as run.py ends its set-up
        server.instrument()
        server.start(traffic.get("max_stream_backlog", 4096))
        sess, host = server.session, server.host
        for seed in [s for _set in range(sets) for s in seeds]:
            client = serve_common.Client(cell, traffic, seed, hold, out_dir,
                                         vocab, longest)
            try:
                summary, _records, _host = serve_common.drive(
                    server, traffic, hold, client, on_open=lambda t: None)
            finally:
                client.kill()
            deadline = time.time() + traffic.get("client_timeout_s", 60.0)
            while sess.active_slots and time.time() < deadline:
                time.sleep(0.2)
            prefills = [p for a in host["admit"] for p in a[2]]
            rows.append({
                "cell": cell.name, "seed": seed,
                "attempted": summary["attempted"],
                "failed": summary["failed"],
                "tokens_per_s": summary["tokens_per_s"],
                "ttft_p50_ms": percentile(summary["ttft_ms"], 50),
                "tpot_p50_ms": percentile(summary["tpot_ms"], 50),
                "prefill_dispatches": len(prefills),
                "prompts": sum(len(p[1]) for p in prefills),
                "prompt_tokens": sum(sum(p[1]) for p in prefills),
                "decode_dispatches": len(host["step"]),
                "pool_conserved": bool(sess.pool_conserved),
                "live_after": len(sess.active_slots)})
            host["admit"][:], host["step"][:] = [], []
            harness.log(json.dumps(rows[-1]))
    finally:
        server.close()
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--hold", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=5,
                    help="of the weights")
    ap.add_argument("--pool", default="",
                    help="a what-if: JSON of pool keys to override")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    entry = importlib.import_module(
        "perfbench.entries." + cell.config["entry"])

    import paddle_tpu as fluid
    from paddle_tpu.core import exec_cache

    fluid.require_accelerator(cell.chips)
    exec_cache.enable_xla_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = hold_windows(cell, entry.common, seeds, args.sets, args.hold,
                        args.seed, fluid.TPUPlace(),
                        pool=json.loads(args.pool) if args.pool else None)
    for k in range(args.sets):
        vals = [r["tokens_per_s"] for r in rows[k * len(seeds):
                                                (k + 1) * len(seeds)]]
        if len(vals) >= 2:
            harness.log("%s set %d, tokens_per_s: median %.4f, spread "
                        "%.4f%% over %d windows: %s"
                        % (cell.name, k + 1, statistics.median(vals),
                           100 * spread(vals), len(vals), vals))
    return 0


if __name__ == "__main__":
    sys.exit(main())
