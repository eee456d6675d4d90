"""Emit the public API signature spec for paddle_tpu.

Reference parity: tools/print_signatures.py + paddle/fluid/API.spec — the
reference locks its Python surface in a golden file so accidental API breaks
fail CI. Usage:

    python tools/print_signatures.py            # print spec to stdout
    python tools/print_signatures.py --update   # rewrite API.spec

The spec line format is ``qualified.name (param, param=default, ...)`` for
functions and ``qualified.name CLASS (init params)`` for classes; defaults
are repr()s so value changes are caught, not just renames.
"""

import argparse
import importlib
import inspect
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

MODULES = [
    "paddle_tpu",
    "paddle_tpu.layers",
    "paddle_tpu.layers.nn",
    "paddle_tpu.layers.tensor",
    "paddle_tpu.layers.control_flow",
    "paddle_tpu.layers.detection",
    "paddle_tpu.layers.sequence",
    "paddle_tpu.layers.io",
    "paddle_tpu.layers.rnn",
    "paddle_tpu.layers.attention",
    "paddle_tpu.layers.loss",
    "paddle_tpu.layers.metric_op",
    "paddle_tpu.layers.nlp",
    "paddle_tpu.layers.learning_rate_scheduler",
    "paddle_tpu.optimizer",
    "paddle_tpu.backward",
    "paddle_tpu.io",
    "paddle_tpu.initializer",
    "paddle_tpu.regularizer",
    "paddle_tpu.clip",
    "paddle_tpu.metrics",
    "paddle_tpu.nets",
    "paddle_tpu.inference",
    "paddle_tpu.data_feeder",
    "paddle_tpu.profiler",
    "paddle_tpu.transpiler",
    "paddle_tpu.parallel_executor",
    "paddle_tpu.reader.decorator",
    "paddle_tpu.evaluator",
    "paddle_tpu.recordio_writer",
    "paddle_tpu.distributed.master",
    "paddle_tpu.elastic.coordinator",
    "paddle_tpu.elastic.reshard",
    "paddle_tpu.elastic.worker",
    "paddle_tpu.dataset.common",
    "paddle_tpu.core.passes",
    # VERDICT r3 Weak #6: the generated unary-activation wrappers and the
    # remaining public-class surface must be under golden protection too
    "paddle_tpu.layers.ops",
    "paddle_tpu.contrib",
    "paddle_tpu.unique_name",
    "paddle_tpu.flags",
    # the top-level fluid surface (fluid.Program, fluid.Executor, ...) is
    # re-exported from these; the package has no __all__, so the golden
    # walks the defining modules
    "paddle_tpu.framework",
    "paddle_tpu.executor",
    "paddle_tpu.core.lod",
    # PR 3: the static-analysis surface (verifier / linter / liveness)
    "paddle_tpu.analysis",
    "paddle_tpu.analysis.diagnostics",
    "paddle_tpu.analysis.verify",
    "paddle_tpu.analysis.lint",
    "paddle_tpu.analysis.liveness",
    "paddle_tpu.debugger",
    # PR 4: the failure-forensics surface (black box / watchdog / NaN
    # provenance) — incident-response APIs are surface too
    "paddle_tpu.observability.blackbox",
    "paddle_tpu.observability.watchdog",
    "paddle_tpu.observability.nan_provenance",
    # PR 5: the recovery surface (checkpoint v2 / sessions / retry /
    # chaos) — what operators script disaster drills against
    "paddle_tpu.resilience.checkpoint",
    "paddle_tpu.resilience.session",
    "paddle_tpu.resilience.retry",
    "paddle_tpu.resilience.chaos",
    # PR 6: the memory surface (live-buffer ledger / memory plan / OOM
    # forensics) — what capacity planning scripts against
    "paddle_tpu.observability.memory",
    # PR 7: the sharding-transpiler surface (derived GSPMD plans + the
    # S001 spec validator) — what distributed recipes script against
    "paddle_tpu.parallel",
    "paddle_tpu.parallel.mesh",
    "paddle_tpu.parallel.sharding",
    "paddle_tpu.analysis.shard_check",
    # PR 8: the serving surface (continuous batching server + the
    # slot-paged decode session + the serving tests' demo model and load)
    "paddle_tpu.serving.server",
    "paddle_tpu.serving.generation",
    "paddle_tpu.serving.loadgen",
    # PR 13: serving resilience — decode snapshots + degradation
    "paddle_tpu.serving.snapshot",
    "paddle_tpu.serving.degradation",
    # PR 14: the network front end — socket serving plane + wire client
    "paddle_tpu.serving.frontend",
    "paddle_tpu.serving.client",
]


def _sig(obj):
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def iter_spec():
    for modname in MODULES:
        mod = importlib.import_module(modname)
        names = getattr(mod, "__all__", None)
        if names is None:
            names = [n for n in dir(mod) if not n.startswith("_")]
            # without __all__, only symbols defined in this module count
            names = [
                n for n in names
                if getattr(getattr(mod, n), "__module__", None) == modname
            ]
        for name in sorted(names):
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj):
                continue
            qual = "%s.%s" % (modname, name)
            if inspect.isclass(obj):
                yield "%s CLASS %s" % (qual, _sig(obj.__init__))
                # public METHODS are surface too (the reference spec
                # lists Program.clone, Executor.run, .minimize, ...):
                # a signature change in one must fail the golden test
                for mname, meth in sorted(vars(obj).items()):
                    if mname.startswith("_"):
                        continue
                    if callable(meth) or isinstance(
                            meth, (staticmethod, classmethod)):
                        fn = meth.__func__ if isinstance(
                            meth, (staticmethod, classmethod)) else meth
                        if callable(fn):
                            yield "%s.%s %s" % (qual, mname, _sig(fn))
            elif callable(obj):
                yield "%s %s" % (qual, _sig(obj))
            else:
                yield "%s CONST %r" % (qual, type(obj).__name__)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--update", action="store_true",
                        help="rewrite API.spec next to this script's repo root")
    args = parser.parse_args()
    lines = list(iter_spec())
    if args.update:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec_path = os.path.join(root, "API.spec")
        header = []
        if os.path.exists(spec_path):
            # '#' annotation lines (deliberate absences vs the reference
            # surface) survive regeneration WHEREVER they sit in the
            # file — all are gathered into the header block
            with open(spec_path) as f:
                header = [line.rstrip("\n") for line in f
                          if line.lstrip().startswith("#")]
        with open(spec_path, "w") as f:
            if header:
                f.write("\n".join(header) + "\n")
            f.write("\n".join(lines) + "\n")
        print("wrote %d signatures to API.spec" % len(lines))
    else:
        sys.stdout.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
