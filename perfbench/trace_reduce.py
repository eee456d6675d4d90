"""From a profiler trace (``.xplane.pb``) to the few things the per-layer
metrics read: busy intervals of each device, the time of each named
operation, the runs of each compiled module, and the idle gaps laid at the
host span that covered them.

Two stages, so that the arithmetic can be checked on a small recorded
trace (``recorded_trace.json``, beside this file):

``flatten(path)``  the trace file -> plain lists (needs only JAX)
``reduce(flat)``   plain lists -> numbers (needs nothing)

What a TPU trace looks like (JAX 0.9, read on a v5e): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per run of a
compiled program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one
event per HLO instruction run, named by its HLO text: ``%name = shape
op(...)``; a ``while`` appears as one event AROUND the events of its
body) and ``Async XLA Ops`` (DMA in flight; not read). Host spans
written with ``jax.profiler.TraceAnnotation`` sit in ``/host:CPU`` on the
same clock; the benchmark prefixes its own with ``pb:``.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "pb:"
NAME_CHARS = 160   # enough for the op's name and its first shape

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?=\s*=|$)")
_FIRST_SHAPE = re.compile(r"=\s*\(?\s*([a-z]+\d*)\[([\d,]*)\]")
# an event that only wraps the events of its body
_WRAPPER = re.compile(r"^(while|conditional|call)$")


def flatten(path):
    """{"devices": {chip: {"modules": [...], "ops": [...]}},
    "host": [...]}; every event is ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    flat = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = flat["devices"].setdefault(
                m.group(1), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                dev[key].extend(
                    [ev.name[:NAME_CHARS], float(ev.start_ns),
                     float(ev.duration_ns)] for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                flat["host"].extend(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events
                    if ev.name.startswith(HOST_PREFIX))
    return flat


def op_name(text):
    """``%flash_attention_fwd.45 = (f32[...`` -> ``flash_attention_fwd``."""
    m = _OP_NAME.match(text.strip())
    return m.group(1) if m else text.strip()[:40]


def first_shape(text):
    """The first result shape of an HLO event name: (dtype, dims) or None."""
    m = _FIRST_SHAPE.search(text)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def union(intervals):
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def _gaps(busy, t0, t1):
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]


def reduce(flat, top=10):
    """The reduction. Times are seconds unless a key says otherwise.

    ``window_s`` runs from the first to the last device event over all
    chips; ``busy_s`` is the union of the ``XLA Ops`` intervals, averaged
    over chips. ``ops`` sums time by operation name without the events
    that only wrap their body. ``modules`` lists every run of a compiled
    program with the kernels found inside it. ``idle_gaps`` lays each idle interval of chip 0
    at the ``pb:`` host span that covered most of it."""
    devices = flat["devices"]
    if not devices:
        return None
    starts = [ev[1] for d in devices.values() for ev in d["ops"]]
    ends = [ev[1] + ev[2] for d in devices.values() for ev in d["ops"]]
    if not starts:
        return None
    t0, t1 = min(starts), max(ends)
    out = {"window_s": (t1 - t0) / 1e9, "chips": len(devices)}

    busy_by_chip, ops_time, ops_calls = {}, {}, {}
    for chip, d in devices.items():
        real = [ev for ev in d["ops"]
                if not _WRAPPER.match(op_name(ev[0]))]
        busy = union([ev[1], ev[1] + ev[2]] for ev in real)
        busy_by_chip[chip] = busy
        for ev in real:
            key = ev[0]
            ops_time[key] = ops_time.get(key, 0.0) + ev[2] / 1e9
            ops_calls[key] = ops_calls.get(key, 0) + 1
    n = float(len(devices))
    out["busy_s"] = sum(total(b) for b in busy_by_chip.values()) / 1e9 / n

    # per operation, summed over chips and averaged: [text, seconds, calls]
    out["ops"] = sorted(([k, v / n, ops_calls[k]]
                         for k, v in ops_time.items()),
                        key=lambda r: -r[1])
    out["device_ops"] = [[_label(k), v] for k, v, _c in out["ops"][:top]]

    # runs of compiled programs on chip 0, with the ops inside each
    first = sorted(devices)[0]
    d0 = devices[first]
    ops_sorted = sorted(d0["ops"], key=lambda ev: ev[1])
    runs = []
    k = 0
    for name, s, dur in sorted(d0["modules"], key=lambda ev: ev[1]):
        inside = {}
        while k < len(ops_sorted) and ops_sorted[k][1] < s:
            k += 1
        j = k
        while j < len(ops_sorted) and ops_sorted[j][1] < s + dur:
            nm = op_name(ops_sorted[j][0])
            if not _WRAPPER.match(nm):
                inside[nm] = inside.get(nm, 0.0) + ops_sorted[j][2] / 1e9
            j += 1
        runs.append({"name": name.split("(")[0], "start_s": (s - t0) / 1e9,
                     "seconds": dur / 1e9, "ops": inside})
    out["modules"] = runs

    # idle gaps of chip 0 against the benchmark's host spans
    spans = sorted(flat["host"], key=lambda ev: ev[1])
    by_name = {}
    for g0, g1 in _gaps(busy_by_chip[first], t0, t1):
        best, best_len = "unattributed", 0.0
        for name, s, dur in spans:
            if s >= g1:
                break
            cov = min(g1, s + dur) - max(g0, s)
            if cov > best_len:
                best, best_len = name[len(HOST_PREFIX):], cov
        if best_len < 0.5 * (g1 - g0):
            best = "unattributed"
        by_name[best] = by_name.get(best, 0.0) + (g1 - g0) / 1e9
    out["idle_gaps"] = sorted(([k, v] for k, v in by_name.items()),
                              key=lambda r: -r[1])[:top]
    out["host_spans"] = [[name[len(HOST_PREFIX):], (s - t0) / 1e9, dur / 1e9]
                         for name, s, dur in spans]
    return out


def _label(text):
    """A short name for the breakdown: the op's name with its number and
    its first result shape, e.g. ``flash_attention_fwd.45_f32_256_8_1_64``."""
    head = text.strip().lstrip("%").split(" ")[0].split("=")[0]
    shape = first_shape(text)
    if shape:
        head += "_%s_%s" % (shape[0], "_".join(str(d) for d in shape[1]))
    return re.sub(r"[^\w.\-]", "_", head)[:64]


def kernel_time(reduced, kernel, where=None):
    """Seconds (per chip) and calls of the events whose operation name
    holds ``kernel`` (a backward kernel runs as
    ``transpose_jvp_<kernel>__``); ``where(dtype, dims)`` filters on the
    first result shape."""
    secs, calls = 0.0, 0
    for text, s, c in reduced["ops"]:
        if kernel not in op_name(text):  # e.g. transpose_jvp_<kernel>__
            continue
        shape = first_shape(text)
        if where is not None and (shape is None or not where(*shape)):
            continue
        secs += s
        calls += c
    return secs, calls


def reduce_file(path):
    return reduce(flatten(path))
