"""Request-scoped distributed tracing for the serving plane.

One trace per request, minted by ``ServingClient`` (a random id plus the
client's send timestamp riding the JSON-lines envelope), continued by
``ServingFrontend`` and the decode session — so a single timeline covers
client send -> queue wait -> admission (slot pop, page acquisition,
prefix-cache hit depth) -> prefill -> every decode dispatch (tokens
committed, speculation accepted, COW copies) -> per-chunk wire flush.

The house overhead contract (telemetry.py's): ``ENABLED`` is a module
bool, flipped by ``FLAGS_request_tracing`` / :func:`enable`. Every hot
path guards on it, so OFF means one attribute read — no per-request
allocations, no wire bytes (the envelope only grows a ``trace`` field
when the CLIENT traces), no fresh-compile delta (tracing is host-side
only; it never touches a program or a feed).

Lifecycle: :func:`start` registers a :class:`Trace` in the in-flight
table (crash forensics: blackbox dumps list these ids); :func:`finish`
closes any still-open spans, derives the per-request SLO attribution
(TTFT, queue/prefill/decode split, inter-token latency distribution,
page-seconds held, tokens-from-speculation fraction, span coverage of
the client-observed wall) and banks the record in a bounded ring —
exported to ``<FLAGS_metrics_path>.traces.jsonl`` by
``telemetry.flush()`` and rendered by ``tools/trace_view.py``
(waterfall + Chrome/Perfetto JSON). Latency histograms carry the ids as
bucket exemplars, so a p99 bucket names a replayable request
(:meth:`metrics_registry.Histogram.observe` ``exemplar=``).

Preemption: a traced request's id lives in the session's
``rid -> trace_id`` binding, which rides the decode snapshot dialect —
a SIGTERM'd process's restored twin re-banks results under the ORIGINAL
ids (continuation traces carry ``origin="session"``).

Rounds: what a request waits for is the decode worker, so under the
same guard every pass of ``ServingFrontend``'s worker loop that did
work is one :class:`Round` in a ring of its own — child spans ``wait``,
``cancel``, ``enqueue``, ``admit``, ``step``, ``handoff`` and, around
each executor call of the session, ``<open span>.dispatch``; each
``name, t0, t1, cpu, parent``. ``cpu`` is the worker thread's
``time.thread_time()`` over the span, read inside both wall stamps on
every span: seconds the thread ran. What is left of the wall, less the
executor's wait for the chip inside the span and less the ``wait``
span's own condition wait, is time the thread was BLOCKED: the
interpreter lock by elimination, beside what else can hide there (a
core that was not free, a ``queue.put``'s mutex, a host-to-device
copy's wait). ``parent``:
index into the round's ``spans``, whose entry 0 is the ``round`` itself
and holds its counts: ``live`` slots and ``backlog`` at the dispatch,
``tokens`` handed to the streams, which the benchmark's report prints
beside the rate the clients counted, and ``handler_cpu`` /
``handler_chunks``, the handler threads' CPU seconds and chunks written
so far (``ServingFrontend`` keeps them a request; a reader takes last
less first over its rounds). A request's ``queue``, ``prefill``
and ``decode.step`` spans name the round they fell into (``round=``), so
``tools/trace_view.py`` counts its queue wait and its decode in rounds.
Every round span is also a ``jax.profiler.TraceAnnotation`` named
``ANNOTATION_PREFIX + name``: free with no profiler session, and inside
one the spans lie in the host plane on the device trace's own clock.
"""

import contextlib
import itertools
import json
import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

from paddle_tpu import flags
from paddle_tpu.observability import lock_witness
from paddle_tpu.observability.metrics_registry import (
    DECODE_BUCKETS,
    REGISTRY,
)

ENABLED = False

RING = 4096  # completed traces kept: a benchmark run's requests (~2500)
ROUND_RING = 4096  # completed rounds kept: a run has under 1000
ANNOTATION_PREFIX = "pt:"  # the program's spans in a profiler trace

_lock = lock_witness.make_lock("observability.tracing")
_inflight = {}                 # trace_id -> Trace
_completed = deque(maxlen=RING)
_rounds = deque(maxlen=ROUND_RING)
_round_ids = itertools.count(1)
_tls = threading.local()       # .round: the Round open on this thread
_NO_SPAN = contextlib.nullcontext()

# inter-token gaps (consecutive chunk flushes of one stream), observed
# at finish() — ms-scale, hence the decode-resolution ladder
_intertoken_seconds = REGISTRY.histogram(
    "paddle_tpu_serving_intertoken_seconds",
    "gap between consecutive streamed token chunks of one traced "
    "request (observed at trace finish; DECODE_BUCKETS resolution)",
    buckets=DECODE_BUCKETS)


def enable(on=True):
    """Flip request tracing; OFF restores the untouched hot path."""
    global ENABLED
    ENABLED = bool(on)


def mint_id():
    """A fresh 16-hex-char trace id (random, not time-derived — ids
    must stay unique across the SIGTERM/restore process boundary)."""
    return os.urandom(8).hex()


class Trace(object):
    """One request's span timeline + accumulators. Mutated from both
    the handler thread (wire flush spans) and the decode worker
    (dispatch spans); list/dict mutation rides the GIL — the module
    lock only guards the in-flight/ring registries."""

    __slots__ = ("id", "origin", "endpoint", "t0", "t_client_send",
                 "spans", "marks", "acc", "done", "_root", "_page_ts")

    def __init__(self, trace_id, endpoint, origin, t_client_send):
        self.id = trace_id
        self.origin = origin
        self.endpoint = endpoint
        self.t0 = time.time()
        self.t_client_send = t_client_send
        self.spans = []
        self.marks = {}
        self.acc = {}
        self.done = False      # set by finish(): the spans are banked
        self._root = None
        self._page_ts = None

    # -- span API -----------------------------------------------------------
    def begin(self, name, **meta):
        sp = {"name": name, "t0": time.time(), "t1": None,
              "meta": meta}
        self.spans.append(sp)
        return sp

    def end(self, sp, **meta):
        sp["t1"] = time.time()
        if meta:
            sp["meta"].update(meta)
        return sp

    def span(self, name, t0, t1, **meta):
        """Append an already-closed span (e.g. queue wait measured from
        an enqueue stamp)."""
        sp = {"name": name, "t0": float(t0), "t1": float(t1),
              "meta": meta}
        self.spans.append(sp)
        return sp

    def mark(self, name):
        """First-occurrence timestamp mark (e.g. ``first_token``)."""
        self.marks.setdefault(name, time.time())

    def bump(self, key, delta=1):
        """Accumulate a derived-stat counter (tokens, tokens_from_spec,
        cow_copies, ...)."""
        self.acc[key] = self.acc.get(key, 0) + delta

    def sample_pages(self, npages):
        """Integrate page-seconds held: called per decode dispatch and
        at release with the slot's CURRENT page count."""
        now = time.time()
        if self._page_ts is not None:
            self.acc["page_seconds"] = (
                self.acc.get("page_seconds", 0.0)
                + npages * (now - self._page_ts))
        self._page_ts = now


def start(trace_id=None, endpoint="generate", origin="frontend",
          t_client_send=None):
    """Register a new in-flight trace (root span opens immediately and
    closes at :func:`finish` — the whole server-side handling window is
    always covered). ``trace_id=None`` mints one."""
    tr = Trace(trace_id or mint_id(), endpoint, origin, t_client_send)
    tr._root = tr.begin("request", endpoint=endpoint, origin=origin)
    with _lock:
        _inflight[tr.id] = tr
    return tr


def inflight_get(trace_id):
    with _lock:
        return _inflight.get(trace_id)


def inflight_ids():
    with _lock:
        return sorted(_inflight)


def _percentile(vals, q):
    if not vals:
        return None
    vals = sorted(vals)
    k = max(0, min(len(vals) - 1,
                   int(round(q / 100.0 * len(vals) + 0.5)) - 1))
    return vals[k]


def _union_seconds(spans, t1_default):
    ivals = sorted((sp["t0"], sp["t1"] if sp["t1"] is not None
                    else t1_default) for sp in spans)
    total, hi = 0.0, None
    for a, b in ivals:
        if hi is None or a > hi:
            total += max(0.0, b - a)
            hi = b
        elif b > hi:
            total += b - hi
            hi = b
    return total


def finish(tr, outcome="ok", **meta):
    """Close the trace: force-close leaked spans (flagged in their
    meta — the cancel/disconnect tests sweep the ring for the flag),
    derive per-request stats, bank the record, drop the in-flight
    entry. Returns the record."""
    now = time.time()
    tr.done = True
    with _lock:
        _inflight.pop(tr.id, None)
    for sp in tr.spans:
        if sp["t1"] is None:
            sp["t1"] = now
            if sp is not tr._root:
                sp["meta"]["force_closed"] = True
    wall = max(now - tr.t0, 1e-9)
    client_wall = (max(now - tr.t_client_send, wall)
                   if tr.t_client_send is not None else wall)
    by_name = {}
    for sp in tr.spans:
        if sp is tr._root:
            continue
        by_name.setdefault(sp["name"], 0.0)
        by_name[sp["name"]] += sp["t1"] - sp["t0"]
    # inter-token gaps: consecutive chunk deliveries — wire flushes for
    # frontend streams, decode dispatches for in-process/session traces
    chunk_ts = sorted(sp["t1"] for sp in tr.spans
                      if sp["name"] == "wire.flush")
    if not chunk_ts:
        chunk_ts = sorted(sp["t1"] for sp in tr.spans
                          if sp["name"] == "decode.step")
    gaps = [b - a for a, b in zip(chunk_ts, chunk_ts[1:])]
    for g in gaps:
        _intertoken_seconds.observe(g, exemplar=tr.id)
    first = tr.marks.get("first_token")
    tokens = tr.acc.get("tokens", 0)
    spec = tr.acc.get("tokens_from_spec", 0)
    stats = {
        "wall_s": round(wall, 6),
        "client_wall_s": round(client_wall, 6),
        "ttft_s": (round(first - (tr.t_client_send
                                  if tr.t_client_send is not None
                                  else tr.t0), 6)
                   if first is not None else None),
        "queue_s": round(by_name.get("queue", 0.0), 6),
        "admit_s": round(by_name.get("admit", 0.0), 6),
        "prefill_s": round(by_name.get("prefill", 0.0), 6),
        "decode_s": round(by_name.get("decode.step", 0.0), 6),
        "flush_s": round(by_name.get("wire.flush", 0.0), 6),
        "intertoken_p50_ms": (round(_percentile(gaps, 50) * 1e3, 3)
                              if gaps else None),
        "intertoken_p95_ms": (round(_percentile(gaps, 95) * 1e3, 3)
                              if gaps else None),
        "intertoken_max_ms": (round(max(gaps) * 1e3, 3)
                              if gaps else None),
        "tokens": tokens,
        "tokens_from_spec": spec,
        "spec_fraction": (round(spec / float(tokens), 4)
                          if tokens else None),
        "page_seconds": round(tr.acc.get("page_seconds", 0.0), 6),
        "cow_copies": tr.acc.get("cow_copies", 0),
        # the acceptance number: fraction of the CLIENT-observed wall
        # the trace's spans account for (root span == the server-side
        # handling window; the remainder is wire + client scheduling)
        "span_coverage": round(
            min(1.0, _union_seconds(tr.spans, now) / client_wall), 4),
    }
    rec = {
        "trace_id": tr.id,
        "endpoint": tr.endpoint,
        "origin": tr.origin,
        "outcome": outcome,
        "t0": tr.t0,
        "t1": now,
        "t_client_send": tr.t_client_send,
        "stats": stats,
        "spans": tr.spans,
    }
    if meta:
        rec.update(meta)
    with _lock:
        _completed.append(rec)
    return rec


# -- the decode worker's rounds ------------------------------------------------

class Round(object):
    """One pass of the decode worker's loop: the ``round`` span (entry 0
    of ``spans``, which also holds the round's counts) and its children.
    Touched by the worker thread only."""

    __slots__ = ("id", "spans", "_open")

    def __init__(self):
        self.id = next(_round_ids)
        self.spans = []
        self._open = []   # (span index, thread_time at begin, annotation)

    def begin(self, name):
        """Open a child of the innermost open span; a ``name`` with a
        leading dot is appended to that span's own (``.dispatch`` inside
        ``admit`` is ``admit.dispatch``)."""
        parent = self._open[-1][0] if self._open else None
        if name[0] == ".":
            name = self.spans[parent]["name"] + name
        ann = TraceAnnotation(ANNOTATION_PREFIX + name)
        ann.__enter__()
        self.spans.append({"name": name, "t0": time.time(), "t1": None,
                           "cpu": None, "parent": parent})
        # the CPU clock is read INSIDE the wall stamps: cpu <= t1 - t0
        self._open.append((len(self.spans) - 1, time.thread_time(), ann))

    def end(self):
        idx, cpu0, ann = self._open.pop()
        sp = self.spans[idx]
        sp["cpu"] = time.thread_time() - cpu0
        sp["t1"] = time.time()
        ann.__exit__(None, None, None)


class _RoundSpan(object):
    """``with`` form of one child span."""

    __slots__ = ("_rd", "_name")

    def __init__(self, rd, name):
        self._rd = rd
        self._name = name

    def __enter__(self):
        self._rd.begin(self._name)

    def __exit__(self, *exc):
        self._rd.end()
        return False


def round_begin():
    """Open a round on the calling thread (the decode worker's, under
    its ``ENABLED`` guard)."""
    rd = _tls.round = Round()
    rd.begin("round")
    return rd


def round_end(rd, keep=True):
    """Close the round with whatever an exception left open and, when
    the pass did work (``keep``), bank it."""
    _tls.round = None
    while rd._open:
        rd.end()
    if keep:
        _rounds.append({"id": rd.id, "spans": rd.spans})


def span(name):
    """``with span("admit"):`` a child span of the round open on this
    thread; nothing with tracing off or no round open (a session driven
    without a frontend) -- one attribute read and a shared no-op
    context."""
    if not ENABLED:
        return _NO_SPAN
    rd = getattr(_tls, "round", None)
    if rd is None:
        return _NO_SPAN
    return _RoundSpan(rd, name)


def round_count(key, delta=1):
    """Add to a count of the round open on this thread: ``live`` (slots
    decoding at the dispatch), ``backlog`` (requests queued then) and
    ``tokens`` (handed to the streams). Call under the ``ENABLED`` guard;
    nothing without a round."""
    rd = getattr(_tls, "round", None)
    if rd is not None:
        root = rd.spans[0]
        root[key] = root.get(key, 0) + delta


def round_id():
    """Id of the round open on this thread, or None."""
    rd = getattr(_tls, "round", None)
    return rd.id if rd is not None else None


def ring_snapshot(ring):
    """A list of a ring (a ``deque``) that its one writer thread may be
    appending to: appends take no lock, so the copy is simply retried."""
    while True:
        try:
            return list(ring)
        except RuntimeError:  # appended to while we copied
            continue


def rounds():
    """The banked rounds, oldest first: ``{"id", "spans"}`` each."""
    return ring_snapshot(_rounds)


def get(trace_id):
    """Resolve a trace id (e.g. a histogram exemplar) to its completed
    ring record, newest first; None when it aged out."""
    with _lock:
        for rec in reversed(_completed):
            if rec["trace_id"] == trace_id:
                return rec
    return None


def completed():
    with _lock:
        return list(_completed)


def write_traces_jsonl(path):
    """One JSON line per completed trace; returns the record count."""
    with _lock:
        recs = list(_completed)
    with open(path, "w") as f:
        for rec in recs:
            f.write(json.dumps(rec) + "\n")
    return len(recs)


def perfetto_events(rec, row=0, pid=1):
    """One completed record -> Chrome/Perfetto ``traceEvents`` (complete
    'X' events, microsecond timestamps; ``row`` is the track the
    request renders on). Shared by tools/trace_view.py and the tests'
    validity check."""
    events = [{
        "name": "trace %s" % rec["trace_id"], "ph": "M",
        "pid": pid, "tid": row, "cat": "__metadata",
        "ts": 0, "args": {"name": rec["trace_id"]},
    }]
    for sp in rec["spans"]:
        args = {"trace_id": rec["trace_id"]}
        args.update(sp.get("meta") or {})
        events.append({
            "name": sp["name"], "ph": "X", "cat": "serving",
            "pid": pid, "tid": row,
            "ts": round(sp["t0"] * 1e6, 3),
            "dur": round(max(sp["t1"] - sp["t0"], 0.0) * 1e6, 3),
            "args": args,
        })
    return events


def reset():
    """Drop every in-flight and completed trace and every round
    (tests)."""
    with _lock:
        _inflight.clear()
        _completed.clear()
    _rounds.clear()


def _init_from_flags():
    try:
        enable(bool(flags.get("request_tracing")))
    except Exception:
        pass


_init_from_flags()
