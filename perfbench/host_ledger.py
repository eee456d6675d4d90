"""The host ledger: where the decode worker's not-running time goes, and
what the threads that share its interpreter cost, read from the PROGRAM's
own account (``program_records.py`` has the rings; this file adds the
arithmetic for the six ``host_`` readers and prints the ledger they come
from). ONE definition for every saturated serving cell: nothing here goes
by a span's name but ``round``, ``wait``, ``handoff`` and the ``.dispatch``
suffix, and the wait for the chip is taken from the executor's dispatch
records.

What the program keeps (``docs/OBSERVABILITY.md``), with tracing on:

* every round span: ``cpu``, the seconds the worker's thread ran;
* every dispatch record: the same by phase, under ``cpu``;
* every round's root: ``handler_cpu`` and ``handler_chunks``, the handler
  threads' CPU seconds (their socket writes' kernel time included, which
  holds no interpreter lock) and the chunks they wrote, as they stood at
  the round's end (a handler writes them when a request of its ends);
* every round's root, who WAKES: ``handler_wakeups`` (the handlers'
  returns from their streams' queues, of the streams that have ended) and
  ``watcher_cancel`` (the cancel verdicts the one thread that reads every
  streaming connection has posted), totals as they stood at the round's
  end, beside the round's own ``cancel_rows`` (slots its cancels
  released).

The split of a wall: **blocked = wall - cpu - device**, ``device`` being
the ``device`` phase (the executor's wait for the chip) of the dispatch
records that end inside the span. For the worker's line the wall is the
round less its ``wait`` span (the worker's own condition wait). So
"blocked" is the interpreter lock BY ELIMINATION: what is neither running,
nor waiting for the chip inside the executor, nor in the worker's own
condition wait. What else can hide in it: a core that was not free (the
chip's host keeps no ``schedstat``, so the wait for a core cannot be told
apart there), a ``queue.put``'s mutex, a host-to-device copy's wait (the
``feed`` and ``fetch`` phases wait for the transfer, not in ``device``), a
page fault's disk, and a caller of the executor that fetches for itself:
what of a ``<span>.dispatch`` span lies OUTSIDE the executor's records is
printed as a column of its own, ``outside``, and subtracted from nothing
(the benchmark's tap in the decoder-only cells runs the executor with
``return_numpy=False`` and waits for the chip in its own ``np.asarray``,
where no record sees it; where that column is most of a span, its
``blocked`` is mostly that wait and no reading of the lock). CPython 3.12
keeps no counter on the lock itself.

A program without the account (the parent of the PR that brought it) reads
None everywhere, as does a run with no device trace (the CPU rehearsals).

BENCHMARK.json lists the six readers of ``layer_metrics/host_*.py`` for
``serve_base_saturated`` alone: in the decoder-only cells ``blocked`` is
mostly the benchmark's own tap fetching for itself (the ``outside``
column), so a reading there would name the wrong thing. The first reader
a traced run calls prints the ledger.
"""

import bisect

from perfbench import harness
from perfbench import metric_lib_glm
from perfbench import program_records as pr

# what of a dispatch is no host work (``program_records.exec_host_seconds``)
NOT_HOST_PHASES = ("device", "compile")
# a ``single`` call that starts this soon after a ``handoff`` span's end
# runs while the handlers it woke write their chunks
AFTER_HANDOFF_S = 0.005
CACHE_KEY = "host_ledger"


# -- the account on spans and records -----------------------------------------

def spans_hold_account(rounds):
    """Whether every span of the rounds holds the thread's ``cpu`` (a
    program before the account takes it on four span names)."""
    return bool(rounds) and all(
        sp.get("cpu") is not None for r in rounds for sp in r["spans"])


def with_account(dispatches):
    """The dispatch records that hold the thread's CPU by phase."""
    return [d for d in dispatches if d.get("cpu") is not None]


class SumsByTime(object):
    """``(stamp, seconds)`` events: ``inside(t0, t1)`` sums the seconds of
    those stamped in between."""

    def __init__(self, events):
        events = sorted(events)
        self._at = [t for t, _s in events]
        self._sum = [0.0]
        for _t, secs in events:
            self._sum.append(self._sum[-1] + secs)

    def inside(self, t0, t1):
        lo = bisect.bisect_left(self._at, t0)
        hi = bisect.bisect_right(self._at, t1)
        return self._sum[hi] - self._sum[lo]


def device_waits(dispatches):
    """The executor's waits for the chip, stamped where the call ends:
    the ``device`` phase of every dispatch record."""
    return SumsByTime((d["t1"], d["phases"].get("device", 0.0))
                      for d in dispatches)


def outside_records(rounds, dispatches):
    """What of every ``<span>.dispatch`` span lies OUTSIDE the executor's
    own records, stamped at the span's end. Such a span wraps one call
    into the executor, so what the records that end inside it do not
    cover is a caller that fetches for itself (the module docstring)."""
    walls = SumsByTime((d["t1"], d["wall_s"]) for d in dispatches)
    return SumsByTime(
        (sp["t1"], max(pr.length(sp) - walls.inside(sp["t0"], sp["t1"]),
                       0.0))
        for r in rounds for sp in r["spans"]
        if sp["name"].endswith(".dispatch"))


def split(wall, cpu, device):
    """``{"wall", "cpu", "device", "blocked"}``."""
    return {"wall": wall, "cpu": cpu, "device": device,
            "blocked": wall - cpu - device}


# -- the worker ---------------------------------------------------------------

def span_table(win):
    """{span name: the split of its spans over the dispatching rounds,
    with ``n`` (spans a round) and ``outside`` (``outside_records``)}."""
    mine = pr.dispatched(win["rounds"])
    out = {}
    for name in sorted({sp["name"] for r in mine for sp in r["spans"]}):
        hits = [sp for r in mine for sp in r["spans"] if sp["name"] == name]
        row = split(sum(pr.length(sp) for sp in hits),
                    sum(sp["cpu"] for sp in hits),
                    sum(win["waits"].inside(sp["t0"], sp["t1"])
                        for sp in hits))
        row["outside"] = sum(win["outside"].inside(sp["t0"], sp["t1"])
                             for sp in hits)
        row["n"] = len(hits) / float(len(mine))
        out[name] = row
    return out


def round_less_wait(rnd, key):
    """The round less its ``wait`` spans under ``key``."""
    return key(rnd["spans"][0]) - sum(
        key(sp) for _i, sp in pr.named(rnd["spans"], "wait"))


def worker_split(win):
    """The split of the dispatching rounds' wall LESS their ``wait``
    spans: what the worker's thread did while it had work, with
    ``outside`` beside it. None without the account."""
    mine = pr.dispatched(win["rounds"])
    if not spans_hold_account(mine):
        return None
    roots = [r["spans"][0] for r in mine]
    out = split(sum(round_less_wait(r, pr.length) for r in mine),
                sum(round_less_wait(r, lambda sp: sp["cpu"]) for r in mine),
                sum(win["waits"].inside(sp["t0"], sp["t1"]) for sp in roots))
    out["outside"] = sum(win["outside"].inside(sp["t0"], sp["t1"])
                         for sp in roots)
    return out if out["wall"] > 0 else None


def shares(parts):
    """The split as percentages of its wall: ``cpu``, ``device`` and
    ``blocked`` sum to 100; ``outside`` overlaps them."""
    return {key: 100.0 * parts[key] / parts["wall"]
            for key in ("cpu", "device", "blocked", "outside")}


# -- the handlers -------------------------------------------------------------

def grown(rounds, key):
    """``(gain, seconds)``: what the roots' running total ``key`` gained
    between the END of the window's first round and the end of its last,
    and the time between those ends. None with fewer than two rounds or
    where a root holds no such total."""
    roots = [r["spans"][0] for r in rounds]
    if len(roots) < 2 or any(key not in root for root in roots):
        return None
    wall = roots[-1]["t1"] - roots[0]["t1"]
    if wall <= 0:
        return None
    return roots[-1][key] - roots[0][key], wall


def handler_line(rounds):
    """``{"wall", "cpu", "chunks", "worker_cpu"}`` over ``grown``'s
    stretch: the handler threads' CPU seconds and their chunks, and the
    worker's own CPU over the same rounds. None where the roots hold no
    account."""
    cpu = grown(rounds, "handler_cpu")
    if cpu is None:
        return None
    return {"wall": cpu[1], "cpu": cpu[0],
            "chunks": grown(rounds, "handler_chunks")[0],
            "worker_cpu": sum(r["spans"][0]["cpu"] for r in rounds[1:])}


def handler_cpu_share(rounds):
    line = handler_line(rounds)
    return None if line is None else 100.0 * line["cpu"] / line["wall"]


def handler_wakeups_per_s(rounds):
    """The handlers' returns from ``stream.q.get()`` a wall second."""
    gain = grown(rounds, "handler_wakeups")
    return None if gain is None else gain[0] / gain[1]


def cancel_verdicts_and_rows(rounds):
    """``(the watcher's cancel verdicts, the slots the rounds' cancels
    released)``, both after the window's first round; None where the
    roots hold no verdicts."""
    gain = grown(rounds, "watcher_cancel")
    if gain is None:
        return None
    return gain[0], sum(r["spans"][0].get("cancel_rows", 0)
                        for r in rounds[1:])


def watcher_verdicts_per_cancel_row(rounds):
    """1.0 says every released slot was a cancel line the watcher read (a
    stream cancelled while still queued has a verdict and no slot; a
    connection whose ``close`` hook or failed write got there first a
    slot and no verdict). A verdict is applied by the worker's NEXT pass,
    so a round's burst at either edge of the window moves the reading by
    a round's share of it. None where nothing was cancelled."""
    both = cancel_verdicts_and_rows(rounds)
    return None if both is None or not both[1] else both[0] / float(both[1])


# -- the executor's calls -----------------------------------------------------

def call_means(dispatches):
    """Means over the host phases (all but ``device`` and ``compile``) of
    the records that hold the account: ``wall_ms``, ``cpu_ms``,
    ``blocked_ms`` (wall less CPU) and ``n``; None without such records."""
    calls = with_account(dispatches)
    if not calls:
        return None
    wall = cpu = 0.0
    for d in calls:
        for phase, secs in d["phases"].items():
            if phase not in NOT_HOST_PHASES:
                wall += secs
                cpu += d["cpu"][phase]
    per_call = 1e3 / len(calls)
    return {"n": len(calls), "wall_ms": per_call * wall,
            "cpu_ms": per_call * cpu, "blocked_ms": per_call * (wall - cpu)}


def handoff_split(rounds, dispatches):
    """The ``single`` calls split by whether a ``handoff`` span ended at
    most ``AFTER_HANDOFF_S`` before they began: ``(after a handoff,
    with the handlers quiet)``, each ``call_means`` or None. The call
    after a ``handoff`` runs beside the handlers that ``handoff`` woke;
    the others are its control."""
    ends = sorted(sp["t1"] for r in rounds
                  for _i, sp in pr.named(r["spans"], "handoff"))
    after, quiet = [], []
    for d in with_account(dispatches):
        if d["origin"] != "single":
            continue
        start = d["t1"] - d["wall_s"]
        k = bisect.bisect_right(ends, start) - 1
        near = k >= 0 and start - ends[k] <= AFTER_HANDOFF_S
        (after if near else quiet).append(d)
    return call_means(after), call_means(quiet)


# -- the report ---------------------------------------------------------------

def _call_line(what, means):
    if means is None:
        return "  %s: none" % what
    return ("  %s: %d calls, host phases %.3f ms wall, %.3f ms CPU, %.3f "
            "ms blocked" % (what, means["n"], means["wall_ms"],
                            means["cpu_ms"], means["blocked_ms"]))


def log_ledger(win):
    """The ledger behind the ``host_`` readers, once a run."""
    rounds, dispatches = win["rounds"], win["dispatches"]
    mine = pr.dispatched(rounds)
    if not spans_hold_account(mine):
        harness.log("host ledger: the program's round spans hold no "
                    "thread account (cpu on every span): nothing to read")
        return
    harness.log("host ledger of the window's %d dispatching rounds, ms a "
                "round (blocked = wall - cpu - device: the interpreter "
                "lock by elimination, the wait for a core inside it; "
                "device = the device phase of the executor's records; "
                "outside = what of a .dispatch span the records do not "
                "cover, subtracted from nothing):" % len(mine))
    harness.log("  %-18s %6s %9s %9s %9s %9s %9s"
                % ("span", "n", "wall", "cpu", "device", "blocked",
                   "outside"))
    ms = 1e3 / len(mine)
    for name, row in span_table(win).items():
        harness.log("  %-18s %6.1f %9.3f %9.3f %9.3f %9.3f %9.3f"
                    % (name, row["n"], ms * row["wall"], ms * row["cpu"],
                       ms * row["device"], ms * row["blocked"],
                       ms * row["outside"]))
    parts = worker_split(win)
    share = shares(parts)
    harness.log("  the worker, of its rounds less their wait (%.3f ms a "
                "round): running %.1f%%, waiting for the chip inside the "
                "executor %.1f%%, blocked %.1f%%; outside the executor's "
                "records %.1f%% (part of the other three: where it is "
                "large, blocked is a caller's own fetch and no reading of "
                "the lock)"
                % (ms * parts["wall"], share["cpu"], share["device"],
                   share["blocked"], share["outside"]))
    line = handler_line(rounds)
    if line is None:
        harness.log("  the handlers: the rounds hold no handler account")
    else:
        worker = line["worker_cpu"] / line["wall"]
        handlers = line["cpu"] / line["wall"]
        harness.log("  the handlers: %.3f CPU seconds a wall second (%.1f "
                    "us of CPU a chunk; %d chunks in %.1f s); with the "
                    "worker's %.3f the interpreter's threads ran %.3f CPU "
                    "seconds a wall second (the interpreter lock gives "
                    "them 1.0 together: what is over it ran with the lock "
                    "released, a socket write's kernel time for one)"
                    % (handlers, 1e6 * line["cpu"] / max(line["chunks"], 1),
                       line["chunks"], line["wall"], worker,
                       worker + handlers))
    both = cancel_verdicts_and_rows(rounds)
    if both is not None:
        harness.log("  who wakes: the handlers %.1f times a second; the "
                    "watcher posted %d cancel verdicts for the %d slots "
                    "the rounds' cancels released"
                    % ((handler_wakeups_per_s(rounds),) + both))
    harness.log(_call_line("executor calls between the rounds",
                           call_means(dispatches)))
    after, quiet = handoff_split(rounds, dispatches)
    harness.log(_call_line("single calls begun within %.0f ms of a "
                           "handoff's end" % (1e3 * AFTER_HANDOFF_S), after))
    harness.log(_call_line("single calls with the handlers quiet", quiet))


# -- what the readers in layer_metrics/ call ----------------------------------

def window_of(rounds, dispatches):
    return {"rounds": rounds, "dispatches": dispatches,
            "waits": device_waits(dispatches),
            "outside": outside_records(rounds, dispatches)}


def window(records):
    """``window_of`` the measured window (the rounds that began inside
    it, and the dispatch records that ended between the first and the
    last of them), or None without a device trace or without rounds.
    Made once a run and kept on ``records``; the first call prints the
    ledger."""
    if CACHE_KEY in records:
        return records[CACHE_KEY]
    out = None
    if pr.traced_on_device(records):
        rounds = pr.program_rounds()
        if rounds:
            rounds = metric_lib_glm.window_rounds(records, rounds)
            out = window_of(rounds, pr.between_rounds(
                pr.program_dispatches() or [], rounds))
            log_ledger(out)
    records[CACHE_KEY] = out
    return out


def read_lockwait_share(records):
    """``blocked`` as a percentage of the worker's rounds less their
    ``wait``."""
    win = window(records)
    parts = None if win is None else worker_split(win)
    return None if parts is None else shares(parts)["blocked"]


def read_rounds_stat(records, stat):
    """``stat`` of the window's rounds: ``handler_cpu_share``,
    ``handler_wakeups_per_s`` or ``watcher_verdicts_per_cancel_row``."""
    win = window(records)
    return None if win is None else stat(win["rounds"])


def read_handler_cpu_share(records):
    return read_rounds_stat(records, handler_cpu_share)


def read_call_mean(records, key):
    """``cpu_ms`` or ``blocked_ms`` of ``call_means``."""
    win = window(records)
    means = None if win is None else call_means(win["dispatches"])
    return None if means is None else means[key]
