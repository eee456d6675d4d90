"""What the per-layer metrics read from the PROGRAM's own records, and the
arithmetic on them: the decode worker's rounds
(``paddle_tpu.observability.tracing.rounds()``), the executor's dispatch
records (``step_profiler.dispatch_records()``), set-up's trace-and-lower
seconds (``exec_cache.stats()``, copied into ``records["cache"]``), and
the program's ``pt:`` annotations in the run's profiler trace.

The readers run in the run's own process after the cell, so the rings are
read by import. The rings hold everything written while tracing was on
(ramp, window and drain); the serving readers keep the rounds that began
inside the measured window (``window_rounds``), the population of the
benchmark's own ``sat_dispatch_gap_p50_ms``: above the knee a quarter of
a run's rounds are edge rounds with fewer admissions, and they moved the
medians by a tenth. A program that has no such ring or counter (the parent
of the PR that brought them) gives None, never an error; so does a run
with no device trace (the CPU rehearsals), like the device metrics, but
for ``trace_lower_s``, a plain counter that needs no trace.

A round is ``{"id", "spans"}``; a span ``{"name", "t0", "t1", "cpu",
"parent"}`` with ``parent`` an index into ``spans`` (entry 0 is the
``round`` itself and carries its counts: ``live`` slots and ``backlog`` at
the dispatch, ``tokens`` handed to the streams). A span's self time is its
length less its children's.
"""

import bisect
import glob
import os

from perfbench import harness, trace_reduce
from perfbench.loadgen import percentile
from perfbench.metric_lib import median

PROGRAM_PREFIX = "pt:"
UNATTRIBUTED = "unattributed"
# idle time under the bare round, below which no child span was open, is
# as unexplained as idle time under no span at all
ROUND = "round"


# the benchmark's span around ``session.step()`` and the program's inside
# it start microseconds apart on one clock
ALIGN_S = 0.002


# -- the program's rings ------------------------------------------------------

def traced_on_device(records):
    return records.get("trace") is not None


def program_rounds():
    """The decode worker's banked rounds, or None where the program
    keeps none."""
    from paddle_tpu.observability import tracing

    rounds = getattr(tracing, "rounds", None)
    return rounds() if rounds is not None else None


def program_dispatches(origin=None):
    """The executor's dispatch records, or None where the program keeps
    none."""
    from paddle_tpu.observability import step_profiler

    read = getattr(step_profiler, "dispatch_records", None)
    return read(origin) if read is not None else None


# -- rounds -------------------------------------------------------------------

def length(span):
    return span["t1"] - span["t0"]


def children(spans, index):
    return [sp for sp in spans if sp["parent"] == index]


def self_times(spans):
    """Each span's length less its children's, in the spans' order."""
    own = [length(sp) for sp in spans]
    for sp in spans:
        if sp["parent"] is not None:
            own[sp["parent"]] -= length(sp)
    return own


def named(spans, name):
    return [(i, sp) for i, sp in enumerate(spans) if sp["name"] == name]


def child_cover(rnd):
    """Share of the round's wall that its child spans cover."""
    spans = rnd["spans"]
    wall = length(spans[0])
    return (sum(length(c) for c in children(spans, 0)) / wall
            if wall > 0 else 1.0)


def host_parts(rnd, key=length):
    """(the round, its ``step.dispatch`` spans, its ``wait`` spans) under
    ``key``: the round's host time is the first less the other two."""
    spans = rnd["spans"]
    return (key(spans[0]),
            sum(key(sp) for _i, sp in named(spans, "step.dispatch")),
            sum(key(sp) for _i, sp in named(spans, "wait")))


def host_seconds(rnd):
    whole, dispatch, wait = host_parts(rnd)
    return whole - dispatch - wait


def dispatched(rounds):
    """The rounds that made a decode dispatch."""
    return [r for r in rounds if named(r["spans"], "step")]


def window_opening(records, rounds):
    """``time.time()`` at the window's opening, or None. The benchmark
    keeps its span around every ``session.step()`` since the opening as
    seconds after it (``records["serve"]["host"]["step"]``) and the
    program a ``step`` span inside the same calls on the same clock, so
    the two lists line up at one offset (steps made after the benchmark
    took its copy are in the program's alone) and the opening is the
    difference."""
    outside = ((records.get("serve") or {}).get("host") or {}).get("step")
    inside = [sp["t0"] for r in rounds
              for _i, sp in named(r["spans"], "step")]
    if not outside:
        return None
    n = len(outside)
    for k in range(len(inside) - n, -1, -1):
        opening = inside[k] - outside[0][0]
        if all(abs(inside[k + i] - outside[i][0] - opening) < ALIGN_S
               for i in range(n)):
            return opening
    return None


def window_rounds(records, rounds):
    """The rounds that began inside the measured window; all of them,
    and a line that says so, where the window cannot be placed."""
    opening = window_opening(records, rounds)
    if opening is None:
        harness.log("rounds: the benchmark's steps do not line up with the "
                    "program's, so ramp and drain rounds are read as well")
        return rounds
    closing = opening + records["serve"]["seconds"]
    return [r for r in rounds if opening <= r["spans"][0]["t0"] < closing]


def median_ms(values):
    return 1e3 * median(values) if values else None


def round_ms_p50(rounds):
    return median_ms([length(r["spans"][0]) for r in dispatched(rounds)])


def round_host_ms_p50(rounds):
    return median_ms([host_seconds(r) for r in dispatched(rounds)])


def span_ms_p50(rounds, name, self_only=False):
    """Median over every span of that name in every round."""
    values = []
    for r in rounds:
        hits = named(r["spans"], name)
        if hits and self_only:
            own = self_times(r["spans"])
            values += [own[i] for i, _sp in hits]
        else:
            values += [length(sp) for _i, sp in hits]
    return median_ms(values)


def per_round_ms_p50(rounds, name):
    """Median over the dispatching rounds of the time their spans of that
    name took together."""
    return median_ms([sum(length(sp) for _i, sp in named(r["spans"], name))
                      for r in dispatched(rounds)])


def offcpu_share(rounds):
    """100 x (1 - cpu / wall), both summed over the dispatching rounds'
    host time: what the worker thread spent not running (waiting for the
    interpreter lock or the scheduler) of the time it had work. The
    program takes ``cpu`` on the spans this needs (``round``, ``wait``,
    ``step.dispatch``); None without them."""
    mine = dispatched(rounds)
    if any(sp["cpu"] is None for r in mine for sp in r["spans"]
           if sp["name"] in ("round", "wait", "step.dispatch")):
        return None
    wall = cpu = 0.0
    for r in mine:
        wall += host_seconds(r)
        whole, dispatch, wait = host_parts(r, key=lambda sp: sp["cpu"])
        cpu += whole - dispatch - wait
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None


# -- dispatch records ---------------------------------------------------------

def exec_host_seconds(rec):
    """All of a dispatch but the wait for the device and the compile."""
    return sum(s for ph, s in rec["phases"].items()
               if ph not in ("device", "compile"))


def exec_host_ms_mean(dispatches):
    if not dispatches:
        return None
    return 1e3 * sum(exec_host_seconds(d)
                     for d in dispatches) / len(dispatches)


def phase_means_ms(dispatches):
    """{phase: mean ms a dispatch}, over all of them."""
    out = {}
    for d in dispatches:
        for ph, secs in d["phases"].items():
            out[ph] = out.get(ph, 0.0) + 1e3 * secs / len(dispatches)
    return out


def log_dispatches(what, dispatches):
    harness.log("executor dispatches %s: %d, the host %.3f ms each in the "
                "mean; by phase: %s"
                % (what, len(dispatches), exec_host_ms_mean(dispatches),
                   ", ".join("%s %.3f" % kv for kv in sorted(
                       phase_means_ms(dispatches).items()))))


def between_rounds(dispatches, rounds):
    """The dispatches that ended between the first round's start and the
    last round's end."""
    if not rounds:
        return []
    t0, t1 = rounds[0]["spans"][0]["t0"], rounds[-1]["spans"][0]["t1"]
    return [d for d in dispatches if t0 <= d["t1"] <= t1]


# -- the program's spans in the profiler's trace ------------------------------

def trace_file(records):
    """The run's ``.xplane.pb`` (``harness.Profiler`` wrote it under the
    cell's output directory), or None."""
    cell = records["cell"]
    paths = sorted(glob.glob(os.path.join(
        cell.root, "perfbench_out", cell.name, "trace", "plugins",
        "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def program_threads(path):
    """The program's annotations in the host planes: one list of ``[name
    without the prefix, start_ns, duration_ns]`` per thread that wrote
    any."""
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [[ev.name[len(PROGRAM_PREFIX):], float(ev.start_ns),
                       float(ev.duration_ns)] for ev in line.events
                      if ev.name.startswith(PROGRAM_PREFIX)]
            if events:
                threads.append(events)
    return threads


def chip0_idle(flat):
    """(idle gaps of the first chip, window start, window end) in ns, as
    ``trace_reduce.reduce`` takes them."""
    devices = flat["devices"]
    ops = [ev for d in devices.values() for ev in d["ops"]]
    if not ops:
        return [], 0.0, 0.0
    t0 = min(ev[1] for ev in ops)
    t1 = max(ev[1] + ev[2] for ev in ops)
    real = [ev for ev in devices[sorted(devices)[0]]["ops"]
            if not trace_reduce._WRAPPER.match(trace_reduce.op_name(ev[0]))]
    busy = trace_reduce.union([ev[1], ev[1] + ev[2]] for ev in real)
    return trace_reduce._gaps(busy, t0, t1), t0, t1


def nest(events):
    """One thread's events as a forest: ``[name, start, end, children]``,
    children in start order (a thread's annotations nest properly)."""
    roots, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        node = [name, start, start + dur, []]
        while stack and stack[-1][2] <= start:
            stack.pop()
        (stack[-1][3] if stack else roots).append(node)
        stack.append(node)
    return roots


def _starts(nodes, memo):
    """The start of each of a node's children, made once per list."""
    got = memo.get(id(nodes))
    if got is None:
        got = memo[id(nodes)] = [n[1] for n in nodes]
    return got


def innermost(nodes, g0, g1, memo):
    """The innermost span that covers most (half or more) of the gap: the
    name, or None when no span at this level does."""
    k = bisect.bisect_right(_starts(nodes, memo), g0) - 1
    best, best_cov = None, 0.0
    for node in nodes[max(k, 0):]:
        if node[1] >= g1:
            break
        cov = min(g1, node[2]) - max(g0, node[1])
        if cov > best_cov:
            best, best_cov = node, cov
    if best is None or best_cov < 0.5 * (g1 - g0):
        return None
    return innermost(best[3], g0, g1, memo) or best[0]


def idle_by_span(gaps, threads):
    """{span name: idle seconds}: each idle gap laid at the innermost
    program span that covers most of it, ``unattributed`` where none
    does."""
    forests = [nest(events) for events in threads]
    out, memo = {}, {}
    for g0, g1 in gaps:
        name = None
        for roots in forests:
            name = innermost(roots, g0, g1, memo)
            if name is not None:
                break
        name = name or UNATTRIBUTED
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e9
    return out


def unattributed_share(by_span):
    """100 x the idle time under no program span, or under the bare round
    alone, over all idle time."""
    whole = sum(by_span.values())
    if not whole:
        return None
    return 100.0 * (by_span.get(UNATTRIBUTED, 0.0)
                    + by_span.get(ROUND, 0.0)) / whole


# -- what the readers in layer_metrics/ call ----------------------------------

def phase_table(rounds):
    """{span name: (spans a dispatching round, median ms a round of their
    lengths together, of their self times together)}."""
    mine = dispatched(rounds)
    own = [self_times(r["spans"]) for r in mine]
    names = sorted({sp["name"] for r in mine for sp in r["spans"]})
    out = {}
    for name in names:
        hits = [named(r["spans"], name) for r in mine]
        out[name] = (
            sum(len(h) for h in hits) / float(len(mine)),
            median_ms([sum(length(sp) for _i, sp in h) for h in hits]),
            median_ms([sum(o[i] for i, _sp in h)
                       for o, h in zip(own, hits)]))
    return out


def round_counts(rounds):
    """{count: its values over the dispatching rounds} for the counts the
    program keeps on a round."""
    return {key: [r["spans"][0][key] for r in dispatched(rounds)
                  if key in r["spans"][0]]
            for key in ("live", "backlog", "tokens", "admit_rows",
                        "cancel_rows")}


def log_rounds(records, rounds):
    """The inside view of a round, for the report's earlier lines."""
    covers = sorted(child_cover(r) for r in dispatched(rounds))
    if not covers:
        return
    harness.log("rounds of the window: %d, %d dispatched; child spans "
                "cover %.1f%% of a round's wall in the median, %.1f%% in "
                "the worst; the worker off the CPU for %.1f%% of its host "
                "time" % (len(rounds), len(covers),
                          100 * percentile(covers, 50), 100 * covers[0],
                          offcpu_share(rounds)))
    for name, (n, total, own) in sorted(phase_table(rounds).items()):
        harness.log("  %-16s %6.1f a round, %9.3f ms together in the "
                    "median, %9.3f ms self" % (name, n, total, own))
    counts = round_counts(rounds)
    # an ``admit`` or a ``cancel`` span is a batch: what the batches held
    # is the round's counts, where the program keeps them
    held = ["%.1f %s (%s)" % (sum(counts[key]) / float(len(covers)), what,
                              key)
            for key, what in (("admit_rows", "requests admitted"),
                              ("cancel_rows", "slots released by cancels"))
            if counts[key]]
    if held:
        harness.log("  a round's batches held, in the mean: "
                    + ", ".join(held))
    if counts["tokens"]:
        # the clients count what they asked for; the worker also hands
        # out what a stream decoded past its client's end
        seconds = (records.get("serve") or {}).get("seconds")
        outside = (records.get("end_to_end") or {}).get("serve_tokens_per_s")
        harness.log("  at a dispatch: %d slots live and %d requests queued "
                    "in the median; %d tokens handed to the streams%s"
                    % (median(counts["live"]), median(counts["backlog"]),
                       sum(counts["tokens"]),
                       "" if not seconds or not outside else
                       " = %.1f tokens/s, against %.1f that the clients "
                       "counted" % (sum(counts["tokens"]) / seconds,
                                    outside)))


def read_rounds(records, stat, *args, **kw):
    """``stat(rounds, *args)`` over the program's rounds; None without a
    device trace, without the ring, or without rounds. ``log=True`` also
    prints the inside view."""
    if not traced_on_device(records):
        return None
    rounds = program_rounds()
    if not rounds:
        return None
    rounds = window_rounds(records, rounds)
    if kw.get("log"):
        log_rounds(records, rounds)
    return stat(rounds, *args)


def read_trace_lower_s(records):
    cache = records.get("cache") or {}
    if "trace_seconds" not in cache:
        return None
    harness.log("set-up until the window's opening: %.3f s tracing Python "
                "to jaxprs, %.3f s lowering them to MLIR, %.3f s compiling "
                "or loading" % (cache["trace_seconds"],
                                cache["lower_seconds"],
                                cache["compile_seconds"]))
    return cache["trace_seconds"] + cache["lower_seconds"]


def read_train_exec_host_ms(records):
    """The window's steps are the last ones the trainer dispatched."""
    steps = (records.get("train") or {}).get("dispatch_seconds")
    if not traced_on_device(records) or not steps:
        return None
    mine = program_dispatches("single")
    if not mine:
        return None
    log_dispatches("of the window's steps", mine[-len(steps):])
    return exec_host_ms_mean(mine[-len(steps):])


def read_serve_exec_host_ms(records):
    if not traced_on_device(records):
        return None
    rounds, dispatches = program_rounds(), program_dispatches()
    if not rounds or not dispatches:
        return None
    mine = between_rounds(dispatches, window_rounds(records, rounds))
    by_origin = {}
    for d in mine:
        by_origin.setdefault(d["origin"], []).append(d)
    for origin, ds in sorted(by_origin.items()):
        log_dispatches("between the rounds, " + origin, ds)
    return exec_host_ms_mean(mine)


def read_idle_unattributed(records):
    if not traced_on_device(records):
        return None
    path = trace_file(records)
    if path is None:
        return None
    threads = program_threads(path)
    # the executor's ``device`` wait is an annotation in any session; with
    # no round among them there is no record to lay the gaps at
    if not any(ev[0] == ROUND for events in threads for ev in events):
        return None
    gaps, _t0, _t1 = chip0_idle(trace_reduce.flatten(path))
    by_span = idle_by_span(gaps, threads)
    harness.log("idle seconds of chip 0 by the innermost program span: %s"
                % ", ".join("%s %.4f" % kv for kv in sorted(
                    by_span.items(), key=lambda kv: -kv[1])))
    return unattributed_share(by_span)
