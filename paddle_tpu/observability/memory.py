"""HBM X-ray: live-buffer ledger, predicted-memory planning, OOM forensics.

PR 2/4 made *time* observable (step telemetry, MFU, stragglers) and PR 4/5
made *failures* observable (black box, NaN provenance, classified retry).
This module does the same for *memory* — the resource every roadmap item
(GSPMD sharding, buffer donation, serving capacity) budgets against:

* **Live-buffer ledger** — the executors, the feed/fetch paths, the
  exec-cache AOT loader and the checkpoint snapshotter register device
  buffers as they enter/leave scopes, classified by kind
  (``param | opt_state | activation | feed | cache``). Exported as
  ``paddle_tpu_hbm_live_bytes{device,kind}`` gauges; a per-step peak
  watermark lands in every telemetry step record (``peak_hbm_bytes``).
  XLA owns the real allocator, so the ledger is the *accountable* view:
  what the framework asked to keep alive, by name — the thing an OOM
  post-mortem needs and ``memory_stats()`` on the backend can't give
  (and on CPU/older runtimes the backend gives nothing at all).

* **Memory plan** — :func:`plan_program` (surfaced as
  ``Program.memory_plan(feed_shapes)``) walks the PR 3 liveness analysis
  with byte accounting in the spirit of ``tools/hlo_cost_model.py``'s
  ``_nbytes`` and reports the predicted high-water mark, the op at which
  it occurs, and the top-K live tensors there. Executors register the
  plan per compiled executable, so predicted-vs-measured peak is a
  first-class report (``profiler.memory_stats()``,
  ``tools/step_breakdown.py --memory``).

* **OOM forensics** — :func:`enrich_and_raise` upgrades a
  ``RESOURCE_EXHAUSTED``-style failure into diagnostic rule **M001**
  (never retried — see resilience/retry.py): a black-box dump carrying
  the ledger's top holders, the predicted peak, and actionable hints
  (enable donation, shrink the batch, shard an axis).

Overhead contract: every executor hook guards on the module bool
``ENABLED`` (mirrors telemetry's switch); ``FLAGS_telemetry=0`` leaves
the hot path untouched. The OOM catch costs one substring check on the
failure path only.
"""

import threading

from paddle_tpu.observability import lock_witness
from paddle_tpu.observability.metrics_registry import REGISTRY

__all__ = [
    "ENABLED", "enable", "reset", "KINDS", "track", "drop",
    "live_bytes", "live_by_kind", "live_by_device", "top_holders",
    "track_state_sharded",
    "take_step_peak", "register_plan", "predicted_peak", "last_plan",
    "plan_program", "MemoryPlan", "is_oom", "MemoryExhaustedError",
    "enrich_and_raise", "RULE", "RULE_NAME",
]

ENABLED = False

KINDS = ("param", "opt_state", "activation", "feed", "cache")

RULE = "M001"
RULE_NAME = "hbm-exhausted"

_lock = lock_witness.make_lock("observability.memory")
_live = {}          # (device, kind, name) -> bytes
_totals = {}        # (device, kind) -> bytes (kept incrementally)
_peak = [0]         # high-water mark of sum(_totals) since take_step_peak
_plans = {}         # fingerprint -> plan dict (bounded FIFO)
_last_plan = [None]
_PLAN_CAP = 64

_live_gauge = REGISTRY.gauge(
    "paddle_tpu_hbm_live_bytes",
    "bytes the framework holds live per device, by buffer kind "
    "(ledger view: params, optimizer state, activations, feeds, caches)",
    labels=("device", "kind"))
_oom_total = REGISTRY.counter(
    "paddle_tpu_oom_total",
    "RESOURCE_EXHAUSTED/OOM failures enriched as M001 diagnostics",
    labels=("origin",))


def enable(on=True):
    """Flip the ledger (telemetry.enable keeps it in lockstep)."""
    global ENABLED
    ENABLED = bool(on)
    return ENABLED


def reset():
    """Drop the ledger, watermark and registered plans (tests)."""
    with _lock:
        for (device, kind) in _totals:
            _live_gauge.set(0, device=device, kind=kind)
        _live.clear()
        _totals.clear()
        _peak[0] = 0
        _plans.clear()
        _last_plan[0] = None


# -- the ledger --------------------------------------------------------------

def track(name, nbytes, kind, device="host"):
    """Register (or replace) one live buffer. Re-tracking the same
    (device, kind, name) key replaces the old entry — the scope-binding
    pattern where a donated buffer's successor takes its name — so the
    ledger balances without an explicit release. Callers guard on
    ``ENABLED``; calling directly always records."""
    nbytes = int(nbytes)
    key = (device, kind, name)
    # Timed acquire [C003]: track/drop run inside the SIGTERM handler
    # chain (snapshot ledger of the final checkpoint), where the signal
    # may have interrupted this very thread mid-ledger-update; the
    # ledger is advisory accounting, so a skipped entry beats a process
    # that cannot die.
    if _lock.acquire(timeout=1.0):
        try:
            old = _live.get(key, 0)
            _live[key] = nbytes
            tot = _totals.get((device, kind), 0) + nbytes - old
            _totals[(device, kind)] = tot
            _live_gauge.set(tot, device=device, kind=kind)
            total = sum(_totals.values())
            if total > _peak[0]:
                _peak[0] = total
        finally:
            _lock.release()
    return key


def drop(name, kind, device="host"):
    """Release one tracked buffer; unknown keys are a no-op (a buffer
    can leave through more than one path — e.g. an async fetch whose
    handle materializes after the sync path already swept)."""
    key = (device, kind, name)
    # timed for the same reason as track() [C003]
    if not _lock.acquire(timeout=1.0):
        return False
    try:
        old = _live.pop(key, None)
        if old is None:
            return False
        tot = _totals.get((device, kind), 0) - old
        _totals[(device, kind)] = tot
        _live_gauge.set(tot, device=device, kind=kind)
    finally:
        _lock.release()
    return True


def live_bytes():
    with _lock:
        return sum(_totals.values())


def live_by_kind():
    out = {}
    with _lock:
        for (_device, kind), b in _totals.items():
            if b:
                out[kind] = out.get(kind, 0) + b
    return out


def live_by_device():
    out = {}
    with _lock:
        for (device, _kind), b in _totals.items():
            if b:
                out[device] = out.get(device, 0) + b
    return out


def top_holders(k=3):
    """The K largest live buffers: ``[{"name", "kind", "device",
    "bytes"}]``, largest first — the first question an OOM autopsy asks."""
    with _lock:
        entries = sorted(_live.items(), key=lambda kv: -kv[1])[:max(0, k)]
    return [{"name": name, "kind": kind, "device": device, "bytes": b}
            for (device, kind, name), b in entries if b]


def take_step_peak():
    """The high-water mark of total ledger bytes since the last call
    (telemetry.record_step's per-step watermark). Resets the mark to the
    CURRENT total so long-lived state keeps counting next step."""
    with _lock:
        peak = _peak[0]
        _peak[0] = sum(_totals.values())
    return peak


# -- executor-facing hooks ---------------------------------------------------

def _state_kinds(cp, program, names):
    """{state var name -> 'param'|'opt_state'}, cached on the compiled
    program (classification walks the graph once per executable)."""
    kinds = getattr(cp, "_mem_kinds", None)
    if kinds is None:
        from paddle_tpu import framework

        block = program.global_block()
        kinds = {}
        for n in names:
            v = block._find_var_recursive(n)
            kinds[n] = ("param" if isinstance(v, framework.Parameter)
                        else "opt_state")
        cp._mem_kinds = kinds
    return kinds


def track_feeds(feeds, device):
    for name, arr in feeds.items():
        track(name, getattr(arr, "nbytes", 0), "feed", device)


def drop_feeds(feeds, device):
    for name in feeds:
        drop(name, "feed", device)


def track_state(cp, program, new_state, device):
    """Scope binding after a dispatch: the step's output state replaces
    the (donated) inputs under the same names, so re-tracking IS the
    release of the consumed buffers."""
    kinds = _state_kinds(cp, program, list(new_state))
    for name, val in new_state.items():
        track(name, getattr(val, "nbytes", 0),
              kinds.get(name, "opt_state"), device)


def track_state_sharded(cp, program, new_state, fallback_device="mesh"):
    """Mesh-path scope binding: book each state var's REAL per-device
    shard bytes under per-device labels, not one mesh-wide logical entry.
    A param sharded over a 4-way ``fsdp`` axis shows ~1/4 of its bytes on
    each device's ``paddle_tpu_hbm_live_bytes{device,kind}`` series while
    replicated state shows full bytes on every device — the measured half
    of the derived-plan story (the predicted half is ``memory_plan`` with
    ``shard_factors``)."""
    from paddle_tpu.observability.telemetry import device_label

    kinds = _state_kinds(cp, program, list(new_state))
    for name, val in new_state.items():
        kind = kinds.get(name, "opt_state")
        try:
            shards = val.addressable_shards
        except Exception:
            shards = None
        if not shards:
            track(name, getattr(val, "nbytes", 0), kind, fallback_device)
            continue
        per_dev = {}
        for sh in shards:
            lbl = device_label(sh.device)
            per_dev[lbl] = per_dev.get(lbl, 0) + int(
                getattr(sh.data, "nbytes", 0))
        for lbl, nb in per_dev.items():
            track(name, nb, kind, lbl)


def track_fetches(fetch_names, fetches, device):
    for name, val in zip(fetch_names, fetches):
        track(name, getattr(val, "nbytes", 0), "activation", device)


def drop_fetches(fetch_names, device):
    for name in fetch_names:
        drop(name, "activation", device)


# -- predicted-memory planning -----------------------------------------------

class MemoryPlan(object):
    """Result of :func:`plan_program`: the predicted high-water mark of
    one step's resident bytes, where it happens, and who holds it.

    Attributes: ``peak_bytes``, ``peak_op_idx`` (index into block 0; the
    peak is measured *entering* that op), ``peak_op_type``, ``n_ops``,
    ``per_op_bytes`` (list, resident bytes entering each op).
    """

    def __init__(self, peak_bytes, peak_op_idx, peak_op_type, n_ops,
                 per_op_bytes, live_at_peak):
        self.peak_bytes = int(peak_bytes)
        self.peak_op_idx = peak_op_idx
        self.peak_op_type = peak_op_type
        self.n_ops = n_ops
        self.per_op_bytes = per_op_bytes
        self._live_at_peak = live_at_peak  # [(name, bytes)] desc

    def top(self, k=5):
        """The K largest tensors live at the predicted peak."""
        return list(self._live_at_peak[:max(0, k)])

    def as_dict(self, top_k=5):
        return {
            "peak_bytes": self.peak_bytes,
            "peak_op_idx": self.peak_op_idx,
            "peak_op_type": self.peak_op_type,
            "n_ops": self.n_ops,
            "top_live": [list(t) for t in self.top(top_k)],
        }

    def __repr__(self):
        return ("MemoryPlan(peak=%d bytes at op %s (%s) of %d)"
                % (self.peak_bytes, self.peak_op_idx, self.peak_op_type,
                   self.n_ops))


def _var_nbytes(block, name, feed_shapes, default_batch):
    """Bytes of one named var: declared shape x dtype itemsize, with feed
    shapes overriding and unknown/dynamic (-1) dims priced at the feed
    batch — the hlo_cost_model ``_nbytes`` discipline applied to VarDescs
    instead of avals."""
    import numpy as np

    from paddle_tpu.core.types import np_dtype

    v = block._find_var_recursive(name)
    if v is None:
        return 0
    shape = (feed_shapes or {}).get(name)
    if shape is None:
        shape = v.shape
    if shape is None:
        return 0
    size = 1
    for d in shape:
        d = int(d)
        size *= d if d > 0 else default_batch
    try:
        item = np.dtype(np_dtype(v.dtype)).itemsize
    except Exception:
        item = 4
    return size * item


def plan_program(program, feed_shapes=None, fetch_names=(),
                 shard_factors=None):
    """Predict one step's HBM high-water mark from the liveness analysis.

    Sweeps block 0's live ranges (analysis/liveness.py): every var is
    resident from its defining op (or op 0 for block inputs: feeds,
    params, state) through its last use (through the whole block when it
    escapes — fetched or persistable). The per-op resident-byte curve's
    maximum is the predicted peak; XLA's scheduler can only do better
    than this program-order bound by reordering, and worse only through
    fragmentation — so it brackets the measured watermark.

    ``shard_factors`` ({var name -> ways split}, from a derived
    GSPMD plan via ``parallel.sharding.plan_shard_factors``) divides
    those vars' bytes, making the predicted peak PER-DEVICE residency
    under the plan instead of logical bytes.
    """
    from paddle_tpu.analysis import liveness

    feed_shapes = {n: tuple(int(d) for d in s)
                   for n, s in (feed_shapes or {}).items()}
    default_batch = 1
    for s in feed_shapes.values():
        if s and int(s[0]) > 0:
            default_batch = max(default_batch, int(s[0]))
    info = liveness.analyze(program, fetch_names=tuple(fetch_names))
    b0 = info.block(0)
    block = program.global_block()
    n_ops = max(1, b0.n_ops)
    # sweep: +bytes at first-def (block inputs at 0), -bytes after last use
    deltas = [0] * (n_ops + 1)
    sizes = {}
    shard_factors = shard_factors or {}
    for name, (d, u) in b0.live_ranges.items():
        nb = _var_nbytes(block, name, feed_shapes, default_batch)
        nb //= max(1, int(shard_factors.get(name, 1)))
        if nb <= 0:
            continue
        start = 0 if d is None else min(d, n_ops - 1)
        v = block._find_var_recursive(name)
        if v is not None and v.persistable:
            # read-modify-write state (a param the optimizer updates) has
            # a first DEF deep in the block, but the buffer arrives as a
            # block input — resident from op 0
            start = 0
        # u is None: defined but never read and not escaping — resident
        # only at its defining op, not through the block's end
        last = max(start, start if u is None else min(u, n_ops - 1))
        sizes[name] = (start, last, nb)
        deltas[start] += nb
        deltas[last + 1] -= nb
    per_op = []
    resident = 0
    for i in range(n_ops):
        resident += deltas[i]
        per_op.append(resident)
    peak_idx = max(range(n_ops), key=lambda i: per_op[i]) if per_op else 0
    peak = per_op[peak_idx] if per_op else 0
    live_at_peak = sorted(
        ((name, nb) for name, (start, last, nb) in sizes.items()
         if start <= peak_idx <= last),
        key=lambda t: -t[1])
    op_type = (block.ops[peak_idx].type
               if 0 <= peak_idx < len(block.ops) else None)
    return MemoryPlan(peak, peak_idx, op_type, n_ops, per_op, live_at_peak)


def register_plan(fingerprint, plan):
    """File one executable's predicted plan (executor, once per compile
    while telemetry is on) so step records and OOM dumps can report
    predicted-vs-measured without recomputing."""
    if not fingerprint or plan is None:
        return
    d = plan.as_dict() if isinstance(plan, MemoryPlan) else dict(plan)
    with _lock:
        _plans[fingerprint] = d
        _last_plan[0] = d
        while len(_plans) > _PLAN_CAP:
            _plans.pop(next(iter(_plans)))


def register_plan_for(cp, program, feed_specs, fingerprint,
                      shard_factors=None, mesh_devices=None):
    """One-shot per compiled executable (executor call sites, guarded on
    telemetry): compute and file the program's predicted plan under its
    telemetry fingerprint. ``shard_factors`` (derived GSPMD plan) makes
    the prediction per-device; pass ``mesh_devices`` alongside so
    ``profiler.memory_stats()`` can scale the per-device peak back to
    the mesh-wide total the measured watermark sums (exact for sharded
    vars, an underestimate for replicated ones — it brackets).
    Best-effort — planning must never break a step."""
    if getattr(cp, "_memory_plan_done", False):
        return None
    cp._memory_plan_done = True
    try:
        plan = plan_program(
            program,
            feed_shapes={n: s for n, (s, _d) in feed_specs.items()},
            fetch_names=cp.fetch_names,
            shard_factors=shard_factors)
    except Exception:
        return None
    d = plan.as_dict()
    if mesh_devices and int(mesh_devices) > 1 and shard_factors:
        d["mesh_devices"] = int(mesh_devices)
    register_plan(fingerprint, d)
    return plan


def predicted_peak(fingerprint=None):
    """Predicted peak bytes for one executable, or — with no fingerprint
    — the most recently registered plan. An explicit fingerprint with no
    registered plan returns None rather than falling back: reporting
    another executable's prediction as this one's would be a silent,
    plausible-looking misattribution in the step records."""
    with _lock:
        if fingerprint is not None:
            plan = _plans.get(fingerprint)
            return plan["peak_bytes"] if plan else None
        if _last_plan[0] is not None:
            return _last_plan[0]["peak_bytes"]
    return None


def last_plan():
    with _lock:
        return dict(_last_plan[0]) if _last_plan[0] else None


def plans():
    with _lock:
        return {k: dict(v) for k, v in _plans.items()}


# -- OOM forensics (rule M001) -----------------------------------------------

# substrings of allocator-failure messages across backends (XLA's
# RESOURCE_EXHAUSTED status, TFRT/PJRT "Out of memory", host MemoryError
# reprs). Deliberately specific: a user ValueError mentioning "memory"
# must not be reclassified.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM when allocating", "failed to allocate")


class MemoryExhaustedError(RuntimeError):
    """A RESOURCE_EXHAUSTED dispatch failure upgraded with forensics:
    ``.diagnostic`` carries the M001 finding (top ledger holders,
    predicted peak). The message keeps the original allocator text, so
    handlers matching RESOURCE_EXHAUSTED still match — and
    resilience/retry.py classifies it never-transient either way."""

    def __init__(self, message, diagnostic=None):
        super(MemoryExhaustedError, self).__init__(message)
        self.diagnostic = diagnostic


def is_oom(exc):
    """True for allocator-exhaustion failures: deterministic for a given
    program and batch, so retrying burns accelerator-hours replaying the
    same death — resilience/retry.py vetoes on this."""
    if isinstance(exc, (MemoryExhaustedError, MemoryError)):
        return True
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


def _fmt_mb(b):
    b = int(b)
    if b >= 10e6:
        return "%.1f MB" % (b / 1e6)
    if b >= 10e3:
        return "%.1f KB" % (b / 1e3)
    return "%d B" % b


def oom_diagnostic(origin="dispatch"):
    """Build the M001 Diagnostic from the current ledger + the last
    registered plan (also used directly by tests/tools)."""
    from paddle_tpu.analysis.diagnostics import Diagnostic

    holders = top_holders(3)
    plan = last_plan()
    parts = ["device memory exhausted during %s: ledger holds %s live"
             % (origin, _fmt_mb(live_bytes()))]
    if holders:
        parts.append("top holders: " + ", ".join(
            "%s (%s, %s, %s)" % (h["name"], h["kind"], h["device"],
                                 _fmt_mb(h["bytes"])) for h in holders))
    if plan:
        parts.append("predicted peak %s entering op %s (%s)"
                     % (_fmt_mb(plan["peak_bytes"]), plan["peak_op_idx"],
                        plan["peak_op_type"]))
    hints = ["enable buffer donation for mutable state (run the training "
             "step, not a clone, so optimizer state updates in place)",
             "shrink the batch / sequence dims of the largest holders"]
    if holders and holders[0]["kind"] == "param":
        hints.append("shard parameters along a mesh axis "
                     "(ParallelExecutor / GSPMD) so each chip holds 1/N")
    elif holders and holders[0]["kind"] == "cache":
        hints.append("bound the executable/AOT caches "
                     "(FLAGS_exec_cache_max_bytes)")
    else:
        hints.append("shard the activation-heavy axis across the mesh, "
                     "or rematerialize (FLAGS_remat_gradients)")
    return Diagnostic(
        RULE, RULE_NAME, "error", "; ".join(parts),
        block_idx=0,
        op_idx=plan["peak_op_idx"] if plan else None,
        op_type=plan["peak_op_type"] if plan else None,
        var_names=tuple(h["name"] for h in holders),
        hint="; ".join(hints))


def enrich_and_raise(exc, origin="dispatch"):
    """The dispatch paths' OOM handler: classify as M001, file the
    finding + ledger snapshot with the black box (and dump), count it,
    and raise :class:`MemoryExhaustedError` chained on the allocator
    error. Never retried: resilience/retry.py classifies OOM (and this
    wrapper) never-transient, so no retry budget is burned replaying a
    deterministic death."""
    from paddle_tpu.observability import blackbox

    diag = oom_diagnostic(origin=origin)
    _oom_total.inc(origin=origin)
    blackbox.record_oom_diagnostic(
        diag, top_holders=top_holders(3),
        predicted_peak_bytes=predicted_peak(),
        live_bytes=live_bytes())
    if blackbox.ENABLED:
        blackbox.dump(reason="oom_diagnostic")
    raise MemoryExhaustedError(
        "%s\n%s\n        hint: %s" % (str(exc), str(diag).split("\n")[0],
                                      diag.hint),
        diagnostic=diag) from exc
