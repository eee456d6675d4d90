"""Run one cell of the benchmark once.

    python perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by the
names in ``BENCHMARK.json`` (see ``perfbench/README.md``). Everything the
run has to say goes on earlier lines; the LAST line of standard output is
the one JSON object of the contract. With no accelerator, or fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # process start, before anything heavy

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


class Context(object):
    """What an entry gets: the cell, the seed, the window's length, the
    devices, the set-up timers and the profiler."""

    def __init__(self, cell, seed, seconds, trace, devices, out_dir,
                 t_start):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.devices, self.out_dir = bool(trace), devices, \
            out_dir
        self.setup = harness.Setup(t_start)
        self.profiler = harness.Profiler(trace, out_dir)
        self.setup_s = None
        self.t_open = None
        self.gc_watch = harness.GcWatch()

    def cache_stats(self):
        from paddle_tpu.core import exec_cache

        return dict(exec_cache.stats())

    def steady(self):
        """End of warm-up: the one full collection that set-up's garbage
        has earned is made here and not at some instant of the window (a
        process that has served for a minute has had it). Nothing is
        frozen: whatever the program allocates from here on is collected
        as the interpreter sees fit, over the whole of the program's heap,
        and every full collection inside the window is reported."""
        gc.collect()
        self.gc_watch.start()

    def window_opened(self, t_open):
        """The first measured instant (``time.perf_counter()``): set-up
        ends here."""
        self.t_open = t_open
        self.setup_s = t_open - self.setup.t_start


def execute(cell, seed, seconds, trace, devices, t_start=None,
            out_dir=None):
    """Run ``cell`` on ``devices``; print the report and the result line;
    return the line as a dict."""
    t_start = T_START if t_start is None else t_start
    out_dir = out_dir or os.path.join(cell.root, "perfbench_out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(cell, seed, seconds, trace, devices, out_dir, t_start)
    ctx.setup.part("import")
    rec = cell.entry().run(ctx)
    used = rec["devices"]
    stalls = ctx.gc_watch.stop(ctx.t_open, seconds)
    harness.log("full collections (generation 2) inside the window: %d, "
                "%.3f s%s" % (len(stalls), sum(d for _t, d in stalls),
                              "".join(" [at %.1f s: %.3f s]" % tuple(s)
                                      for s in stalls)))

    parts = ctx.setup.parts
    harness.log("setup_s %.3f taken apart: %s" % (
        ctx.setup_s, ", ".join("%s %.3f" % p for p in parts)))
    cache = rec["cache"]
    harness.log("compile cache at the window's opening: %d hits, %d misses,"
                " %.3f s compiling or loading (dir %s)" % (
                    cache["persistent_hits"], cache["persistent_misses"],
                    cache["compile_seconds"], cache["xla_cache_dir"]))
    inside = ctx.cache_stats()["backend_compiles"] - cache["backend_compiles"]
    harness.log("programs compiled inside the measured window: %d%s"
                % (inside, "" if not inside else
                   " -- NOT STEADY: warm that shape up in set-up"))
    if cache["persistent_misses"]:
        harness.log("NOT WARM: %d programs were compiled in this run's "
                    "set-up; a second run here finds them in the cache"
                    % cache["persistent_misses"])

    device = harness.device_record(used)
    breakdown = None
    if trace:
        reduced = ctx.profiler.reduce()
        records = dict(rec, cell=cell, config=cell.config,
                       traffic=cell.traffic, trace=reduced,
                       setup=dict(parts), setup_s=ctx.setup_s,
                       peaks=harness.peak_for(cell.peaks, device["kind"]),
                       chips=len(used))
        metrics = harness.read_layer_metrics(cell, records)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(rec["end_to_end"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end()}
    harness.result_line(rec["correct"], rec["attempted"], rec["failed"],
                        metrics, device, breakdown)
    return {"correct": bool(rec["correct"]), "metrics": metrics, "device": device}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)

    import paddle_tpu as fluid
    from paddle_tpu.core import exec_cache

    # raises (exit code 1, no result line) with no accelerator or fewer
    # chips than the cell asks for; the CPU is never an accelerator here
    devices = fluid.require_accelerator(cell.chips)
    if devices[0].platform == "cpu":
        raise fluid.NoAcceleratorError("the benchmark needs an accelerator")
    # JAX's persistent cache at JAX_COMPILATION_CACHE_DIR if the machine
    # sets it, else the fixed <checkout>/.jax_cache
    harness.log("compile cache: %s" % exec_cache.enable_xla_cache())
    execute(cell, args.seed, args.seconds, args.trace,
            list(devices)[:cell.chips])
    return 0


if __name__ == "__main__":
    sys.exit(main())
