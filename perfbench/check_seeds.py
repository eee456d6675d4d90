"""Read, in ONE process and under one set-up, the numbers that decide
``correct`` for a cell over many seeds, and the control's.

    python perfbench/check_seeds.py --workload <name> --seeds 1,2,3 [--control-seeds 4,5,6]

For each seed it prints the numbers the run compares with the plain
reference; for each control seed, the same numbers with the reference
itself in the program's place, computed one precision below the
configuration's. The limits in the configuration's file are set from
these two readings (PERF.md, section 2). The benchmark's own runs never
run the control.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)

    import paddle_tpu as fluid
    from paddle_tpu.core import exec_cache

    devices = fluid.require_accelerator(cell.chips)
    exec_cache.enable_xla_cache()
    checker = cell.entry().make_checker(cell, list(devices)[:cell.chips])
    rows = []
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            fn = (checker.numbers if kind == "program"
                  else checker.control_numbers)
            row = dict(fn(seed), kind=kind, seed=seed)
            rows.append(row)
            harness.log(json.dumps(row))
    keys = [k for k in rows[0] if isinstance(rows[0][k], float)]
    for key in keys:
        prog = [r[key] for r in rows if r["kind"] == "program"]
        ctrl = [r[key] for r in rows if r["kind"] == "control"]
        harness.log("%s: program max %.6g over %d seeds; control min %s "
                    "over %d seeds" % (key, max(prog), len(prog),
                                       ("%.6g" % min(ctrl)) if ctrl else "-",
                                       len(ctrl)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
