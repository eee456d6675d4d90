"""What every cell shares: finding the cell's files by the names in
``BENCHMARK.json``, set-up timers, the profiler window, the per-layer
metric readers and the result line."""

import glob
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(*parts):
    print(*parts, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell(object):
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, workload, root=ROOT, config=None, traffic=None):
        """``config``/``traffic`` stand in for the files (the tests' tiny
        sizes)."""
        self.root = root
        self.dir = os.path.join(root, "perfbench")
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit("no workload %r in BENCHMARK.json (have %s)"
                             % (workload, sorted(cells)))
        self.spec = cells[workload]
        self.name = workload
        self.chips = int(self.spec["chips"])
        cfg = {c["name"]: c for c in self.bench["configs"]}[
            self.spec["config"]]
        self.config = config or load_json(os.path.join(root, cfg["file"]))
        self.traffic = traffic or load_json(os.path.join(
            self.dir, "traffic", self.spec["traffic"] + ".json"))
        self.peaks = load_json(os.path.join(self.dir, "peaks.json"))

    def _listed(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._listed(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self._listed(m)]

    def entry(self):
        """The module that runs this configuration's entry point."""
        name = self.config["entry"]
        return load_module(os.path.join(self.dir, "entries", name + ".py"),
                           "perfbench_entry_" + name)


class Setup(object):
    """``setup_s`` taken apart: consecutive named parts from process
    start to the first measured instant."""

    def __init__(self, t_start):
        self.t_start = t_start
        self.parts = []
        self._t = t_start

    def part(self, name):
        now = time.perf_counter()
        self.parts.append((name, now - self._t))
        self._t = now

    def total(self):
        return self._t - self.t_start

    def seconds(self, *names):
        return sum(s for n, s in self.parts if n in names)


class GcWatch(object):
    """The interpreter's full collections (generation 2) while it is on:
    ``[offset from t_open, seconds]`` each. They walk every object the
    program holds (its IR, JAX's traces), so one inside the window is a
    stall of the step or the decode round it lands in."""

    def __init__(self):
        self.full = []
        self._t0 = None

    def _event(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.full.append([self._t0, time.perf_counter() - self._t0])
            self._t0 = None

    def start(self):
        import gc

        gc.callbacks.append(self._event)

    def stop(self, t_open, seconds):
        """Stop watching; the collections that began in the window."""
        import gc

        gc.callbacks.remove(self._event)
        return [[t - t_open, d] for t, d in self.full
                if 0.0 <= t - t_open < seconds]


def device_record(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def peak_for(peaks, kind):
    """The chip's published peaks; a device that is not in the table is an
    error, never a default."""
    for row in peaks["chips"]:
        if row["device_kind"].lower() == kind.lower():
            return row
    raise KeyError("no peaks for device kind %r in perfbench/peaks.json"
                   % kind)


# -- the profiler window ------------------------------------------------------

class Profiler(object):
    """Trace a short stretch of the window. ``annotate(name)`` puts a host
    span on the profiler's own clock, so an idle gap on the device can be
    laid at what the host was doing."""

    def __init__(self, on, out_dir):
        self.on = bool(on)
        self.dir = os.path.join(out_dir, "trace")
        self.running = False
        self.window_s = None
        self._t0 = None

    def start(self):
        if not self.on:
            return
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        # the Python tracer would record every call of every client and
        # handler thread; the benchmark's own spans are annotations
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._t0 = time.perf_counter()
        self.running = True

    def stop(self):
        if not self.running:
            return
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.running = False

    def annotate(self, name):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def reduce(self):
        """The reduced trace (``trace_reduce.reduce_file``) or None."""
        if not self.on:
            return None
        from perfbench import trace_reduce

        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise RuntimeError("the profiler wrote no trace under %s"
                               % self.dir)
        return trace_reduce.reduce_file(paths[-1])


# -- per-layer metrics --------------------------------------------------------

def read_layer_metrics(cell, records):
    """{name: {"value", "unit"}} for this cell's per-layer metrics: each is
    read by ``layer_metrics/<name>.py``; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out = {}
    for m in cell.per_layer():
        path = os.path.join(cell.dir, "layer_metrics", m["name"] + ".py")
        reader = load_module(path, "perfbench_metric_" + m["name"])
        value = reader.read(records)
        if value is None:
            log("per-layer metric %s: nothing to read" % m["name"])
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct, attempted, failed, metrics, device,
                breakdown=None):
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
