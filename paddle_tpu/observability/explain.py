"""Recompile explainer: "why did it retrace" as one structured log line.

Every executable-cache key is a tuple of independent components (program
structure fingerprint, feed shape/dtype specs, fetch set, scope
signature, trace-time flags, device). When a run misses every in-memory
cache layer and pays a fresh XLA trace, the executor calls
:func:`record_compile` with those components; the explainer diffs them
against the NEAREST previously-compiled entry (most components in
common) and emits a structured event naming exactly which component(s)
forced the recompile — the debugging session TensorFlow-era retrace
hunts used to cost, reduced to reading a log line.

Events go to the ``paddle_tpu.observability.explain`` logger as JSON, to
the metrics registry (``paddle_tpu_recompiles_total{changed=...}``), and
to a bounded in-process list (:func:`events`) for tests and tooling.
Always on: the cost is one dict diff per *compile*, never per step.

The event also says WHO asked for the executable (docs/OBSERVABILITY.md,
"The set-up ledger"): ``seq`` (its index over the ring's lifetime),
``ops`` (the program's operator count) and ``label``/``span``: the path
and index of a SET-UP SPAN. :func:`setup_span` is a named span around the
code that BUILDS a program or makes its first run (the two serving
sessions' constructors and builders); a program built under one is
stamped with the span's path, so an executable first asked for later, by
the warm-up with no span open, still carries the name. Stamps (``ts`` of
a record, ``t0``/``t1`` of a span) are ``time.perf_counter()`` moved onto
``time.time()``'s epoch by one offset taken at import, so they lie beside
the dispatch records' and the round spans' stamps.
"""

import collections
import json
import logging
import functools
import threading
import time

from paddle_tpu.observability import lock_witness
from paddle_tpu.observability.metrics_registry import REGISTRY

__all__ = ["record_compile", "events", "reset", "COMPONENTS",
           "COMPONENT_LINT_RULES", "setup_span", "setup_spans",
           "name_program", "spanned", "stamp"]

logger = logging.getLogger("paddle_tpu.observability.explain")

# Diffable cache-key components, in blame-priority order: when several
# differ vs. the nearest entry, all are reported, first is the headline.
COMPONENTS = ("program", "feed_specs", "fetch_names", "scope_signature",
              "flags", "device", "mode")

# Blamed component -> the retrace-hazard lint rule(s) (analysis/lint.py)
# that statically predict that kind of miss. Events carry the ids so a
# hot recompile loop in a log names the rule to run the linter for:
#   feed_specs   churn <- L001 dynamic-feed-shape
#   program      churn <- L002 literal-scalar-attr (attr literals re-baked
#                 per step) / L003 nondeterministic-names (fingerprint
#                 drifts with unique_name counters)
#   fetch_names  churn <- L004 fetch-list-churn
COMPONENT_LINT_RULES = {
    "feed_specs": ("L001",),
    "program": ("L002", "L003"),
    "fetch_names": ("L004",),
}

_MAX_EVENTS = 512
# Bounded diff window: nearest-entry search is O(len) under the lock on
# every compile, and this module is always on — a serving process
# compiling many distinct feed shapes must not accumulate component
# dicts forever. 256 recent compiles is plenty of context to blame
# against; older ones age out (a miss against an aged-out entry reads
# as first_compile-ish blame on whichever components differ).
_MAX_ENTRIES = 256

_lock = lock_witness.make_lock("observability.explain")
_entries = collections.deque(maxlen=_MAX_ENTRIES)  # recent compile keys
_events = []     # bounded structured event log
_compile_count = [0]
_MAX_SPANS = 1024
_spans = []      # bounded set-up spans, beside the records
_span_base = [0]  # spans dropped off the front: indices stay lifetime's
_tls = threading.local()
# time.perf_counter() + _EPOCH reads as time.time() did at import
_EPOCH = time.time() - time.perf_counter()


def stamp(perf=None):
    """``time.perf_counter()`` (now, or ``perf``) on ``time.time()``'s
    epoch: the clock of every ``t0``/``t1`` here."""
    return (time.perf_counter() if perf is None else perf) + _EPOCH

_recompiles = REGISTRY.counter(
    "paddle_tpu_recompiles_total",
    "fresh XLA traces by blamed cache-key component",
    labels=("changed",))


def _canon(components):
    out = {}
    for k in COMPONENTS:
        v = components.get(k)
        if isinstance(v, (set, frozenset)):
            v = tuple(sorted(v))
        elif isinstance(v, list):
            v = tuple(v)
        out[k] = v
    return out


def _describe_change(key, old, new):
    """Human detail for the headline components; terse repr otherwise."""
    if key == "feed_specs":
        old_d, new_d = dict(old or ()), dict(new or ())
        parts = []
        for name in sorted(set(old_d) | set(new_d)):
            a, b = old_d.get(name), new_d.get(name)
            if a != b:
                parts.append("%s: %s -> %s" % (name, a, b))
        return "; ".join(parts) or "feed set changed"
    if key == "flags":
        old_d, new_d = dict(old or ()), dict(new or ())
        return "; ".join(
            "%s: %r -> %r" % (n, old_d.get(n), new_d.get(n))
            for n in sorted(set(old_d) | set(new_d))
            if old_d.get(n) != new_d.get(n))
    if key == "program":
        return "program structure changed (fingerprint %s -> %s)" % (
            str(old)[:12], str(new)[:12])
    if key == "scope_signature":
        old_s, new_s = set(old or ()), set(new or ())
        added, gone = sorted(new_s - old_s), sorted(old_s - new_s)
        bits = []
        if added:
            bits.append("vars added: %s" % ", ".join(added[:6]))
        if gone:
            bits.append("vars removed: %s" % ", ".join(gone[:6]))
        return "; ".join(bits) or "scope signature changed"
    return "%r -> %r" % (old, new)


# -- set-up spans ------------------------------------------------------------

class setup_span(object):
    """``with setup_span("cow/4"):`` around code that builds a program or
    makes its first run. Kept in a bounded list (:func:`setup_spans`) as
    ``{"index", "name", "path", "t0", "t1", "parent"}``: ``index`` counts
    spans over the list's lifetime and ``parent`` is the enclosing span's
    (None at a root); ``path`` joins the names from the thread's
    outermost open span down with ``/`` and is what a record asked for
    under the span takes as its ``label``. ``program`` is stamped with
    the path (``name_program``), so an executable that is first run
    later, with no span open, still carries the name. Two clock reads;
    always on, like the events: set-up only, never a step."""

    __slots__ = ("name", "program", "_index")

    def __init__(self, name, program=None):
        self.name, self.program = name, program

    def __enter__(self):
        parent, above = _innermost()
        path = self.name if above is None else above + "/" + self.name
        span = {"name": self.name, "path": path, "t0": stamp(),
                "t1": None, "parent": parent}
        self._index = _keep_span(span)
        _tls.stack.append((self._index, path))
        if self.program is not None:
            self.program._setup_label = path
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()
        _close_span(self._index)
        return False


def _keep_span(span):
    """Append; returns the span's ``index`` (the list drops its older
    half when full, the count goes on)."""
    with _lock:
        span["index"] = _span_base[0] + len(_spans)
        _spans.append(span)
        if len(_spans) > _MAX_SPANS:
            del _spans[:_MAX_SPANS // 2]
            _span_base[0] += _MAX_SPANS // 2
        return span["index"]


def _close_span(index):
    with _lock:
        at = index - _span_base[0]
        if at >= 0:
            _spans[at]["t1"] = stamp()


def _innermost():
    """(index, path) of this thread's innermost open span, or (None,
    None)."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack[-1] if stack else (None, None)


def name_program(program):
    """Stamp ``program`` with the innermost open span's path, as
    ``setup_span(name, program)`` does for a program it is given: the
    ``label`` of the executables made from it, whenever they are first
    asked for. Returns the program."""
    program._setup_label = _innermost()[1]
    return program


def spanned(name):
    """Decorator: every call runs under ``setup_span(name)`` (a
    session's constructor under its root ``session.init``)."""
    def wrap(fn):
        @functools.wraps(fn)
        def under_span(*args, **kwargs):
            with setup_span(name):
                return fn(*args, **kwargs)
        return under_span
    return wrap


def setup_spans():
    """The set-up spans (oldest first, bounded), as copies."""
    with _lock:
        return [dict(sp) for sp in _spans]


# -- the record --------------------------------------------------------------

def record_compile(components, forced=False, program=None):
    """One fresh XLA trace. ``components`` maps COMPONENTS keys to the
    new cache-key pieces; ``forced`` marks use_program_cache=False
    bypasses (nothing to blame — the caller asked). ``program`` gives
    the event its ``ops`` and, where it was built under a set-up span,
    its ``label``. Returns the event."""
    comp = _canon(components)
    now = stamp()
    span, label = _innermost()
    if program is not None:
        label = getattr(program, "_setup_label", None) or label
    with _lock:
        nearest = None
        nearest_score = -1
        for entry in _entries:
            score = sum(1 for k in COMPONENTS if entry[k] == comp[k])
            if score > nearest_score:
                nearest, nearest_score = entry, score
        _entries.append(comp)
        _compile_count[0] += 1
        n_compiles = _compile_count[0]
    if forced:
        changed = ["forced_refresh"]
        detail = {"forced_refresh": "use_program_cache=False bypass"}
    elif nearest is None:
        changed = ["first_compile"]
        detail = {"first_compile":
                  "no prior executable in this process to compare against"}
    else:
        changed = [k for k in COMPONENTS if nearest[k] != comp[k]]
        detail = {k: _describe_change(k, nearest[k], comp[k])
                  for k in changed}
        if not changed:
            # identical key components but the in-memory registry missed:
            # an LRU eviction or a purged cache — name that, don't blame
            # the program
            changed = ["cache_evicted"]
            detail = {"cache_evicted":
                      "key matches a prior compile; the in-memory entry "
                      "was evicted or purged"}
    lint_rules = [r for c in changed
                  for r in COMPONENT_LINT_RULES.get(c, ())]
    event = {
        "event": "fresh_compile",
        "ts": now,
        "changed": changed,
        "detail": detail,
        "lint_rules": lint_rules,
        "lint_rule": lint_rules[0] if lint_rules else None,
        "program_fingerprint": str(comp.get("program"))[:16],
        "mode": comp.get("mode"),
        "device": comp.get("device"),
        "compiles_so_far": n_compiles,
    }
    event.update(
        seq=n_compiles - 1, label=label, span=span,
        ops=(sum(len(b.ops) for b in program.blocks)
             if program is not None else None))
    with _lock:
        _events.append(event)
        del _events[:-_MAX_EVENTS]
    _recompiles.inc(changed=changed[0])
    logger.info("recompile: %s", json.dumps(event, sort_keys=True))
    return event


def events():
    """The structured event log (oldest first, bounded)."""
    with _lock:
        return [dict(e) for e in _events]


def reset():
    """Forget prior compiles, events and spans (tests)."""
    with _lock:
        _entries.clear()
        del _events[:]
        _compile_count[0] = 0
        del _spans[:]
        _span_base[0] = 0
