"""Training-step observatory: phase-attributed step timelines.

Telemetry (``observability/telemetry.py``) prices a step as one wall
number; this module says where the time went. Every profiled step is a
record of phase spans —

* ``input_wait`` — consumer-side reader/queue starvation, measured at
  the source (``layers/io.py`` / ``reader/decorator.py`` call
  :func:`note_input_wait`; a thread-local accumulator hands the wait to
  the NEXT step that thread runs, so prefetch-thread waits are never
  mis-billed to the training thread),
* ``feed`` — host feed conversion + host->device transfer,
* ``compile`` — executable lookup (cache hit = microseconds; a fresh
  XLA trace shows up here instead of silently fattening the step),
* ``dispatch`` — the jitted call itself (argument marshalling + XLA
  enqueue; chaos' ``exec.dispatch`` faults land inside this bracket),
* ``device`` — block_until_ready on the fetched arrays (a
  ``jax.profiler.TraceAnnotation``, ``pt:device``: whoever opened a
  profiler session finds the bracket on the device trace's clock),
* ``fetch`` — device->host materialization to numpy,
* ``host`` — the residual (record bookkeeping, scope writes, python).

Roofline join: once per executable the step function is re-traced (off
the timed path) and priced by tools/hlo_cost_model.py's fused-group
table — per-step FLOPs, HBM bytes, roofline-predicted time, memory- vs
compute-bound verdict. Each record then carries achieved-FLOP/s,
achieved-MFU and achieved-vs-predicted, and classifies itself
``input`` / ``host`` / ``compute`` / ``bandwidth`` bound.

On top of the stream: a bounded ring exported as
``<metrics_path>.stepprof.jsonl`` through ``telemetry.flush()``,
metrics-registry surfaces (phase histograms, starvation + achieved-MFU
gauges), and an online regression detector — rolling median + MAD per
executable; excursions and sustained drifts emit black-box flight
events naming the guilty phase.

The dispatch record: the phase brackets themselves are always on. Every
executor dispatch leaves ``(origin, end stamp on time.time(), wall
seconds, seconds by phase)`` in a ring of its own
(:func:`dispatch_records`), flag or no flag — one StepSpan and a dozen
perf_counter calls, microseconds against a dispatch of milliseconds —
so a benchmark can read the host's share of a dispatch from a run that
switched nothing on. With request tracing on
(``observability/tracing.ENABLED``, read once a dispatch in
:func:`begin`) the record also says how long the calling thread RAN in
each phase (``time.thread_time()`` at every bracket). A phase's wall
less its CPU is time the thread was blocked; in ``device`` that is the
wait for the chip, anywhere else the interpreter lock by elimination.

Overhead contract (FLAGS_step_profile, telemetry's discipline): OFF
adds nothing to the dispatch record — no ring record, no cost join, no
detector, no metric write, zero fresh compiles, bit-identical results.
ON adds those per step; the cost-model trace is one-shot per executable
and runs after the timed region.
"""

import collections
import threading
import time

from jax.profiler import TraceAnnotation

from paddle_tpu.observability import lock_witness
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.observability.metrics_registry import REGISTRY

__all__ = [
    "ENABLED", "enable", "reset", "begin", "finish", "records",
    "inflight", "note_input_wait", "note_queue_wait", "cost_table",
    "write_stepprof_jsonl", "StepSpan", "PHASES", "RING_CAP",
    "device_annotation", "dispatch_records", "DISPATCH_RING_CAP",
    "DROP_ON_ERROR",
]

ENABLED = False

RING_CAP = 2048
DISPATCH_RING_CAP = 16384  # a benchmark run makes ~10 000 dispatches

# phase vocabulary — the record's "phases" dict only carries nonzero
# entries, but the consumer (tools/step_breakdown.py) treats this tuple
# as the full axis
PHASES = ("input_wait", "feed", "compile", "dispatch", "device", "fetch",
          "host")

# regression detector: rolling per-executable baseline
_REG_WINDOW = 64     # samples in the rolling median/MAD window
_REG_MIN = 8         # baseline size before the detector speaks
_REG_K = 5.0         # MAD multiplier (5 sigma-equivalents) for excursions
_REG_REL_FLOOR = 0.25  # minimum relative excess — sub-ms steps are noisy
_DRIFT_N = 5         # consecutive excursions = sustained drift, rebase

_lock = lock_witness.make_lock("observability.step_profiler")
_records = collections.deque(maxlen=RING_CAP)
# every dispatch, flag or no flag: (origin, t_end, wall_s, phases, the
# thread's CPU seconds by phase or None)
_dispatches = collections.deque(maxlen=DISPATCH_RING_CAP)
_cost = {}           # fingerprint -> per-step cost join (None = tried, failed)
_reg = {}            # fingerprint/origin -> regression baseline state
_tls = threading.local()   # .input_wait: seconds banked for the next step
# thread ident -> (origin, phase, t_phase, t_step): the in-flight step's
# current bracket, read lock-free by watchdog/blackbox (single-key dict
# ops are atomic under the GIL; a racy read is fine for forensics)
_inflight = {}

# same bucket ladder as telemetry's step histogram: phases span the same
# 100us..100s range a step does
_PHASE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                  0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                  50.0, 100.0)

_phase_seconds = REGISTRY.histogram(
    "paddle_tpu_step_phase_seconds",
    "per-step wall seconds attributed to each phase", labels=("phase",),
    buckets=_PHASE_BUCKETS)
_achieved_mfu = REGISTRY.gauge(
    "paddle_tpu_step_achieved_mfu",
    "achieved MFU of the last profiled step (cost-model FLOPs / wall / "
    "peak)")
_starvation = REGISTRY.gauge(
    "paddle_tpu_step_starvation_fraction",
    "input-wait fraction of the last profiled step's wall")
_regressions = REGISTRY.counter(
    "paddle_tpu_step_regressions_total",
    "step-time excursions/drifts flagged by the online detector",
    labels=("kind", "phase"))
_reader_wait = REGISTRY.counter(
    "paddle_tpu_reader_wait_seconds_total",
    "consumer-side seconds blocked waiting on reader queues",
    labels=("site",))
_queue_depth = REGISTRY.gauge(
    "paddle_tpu_reader_queue_depth",
    "items in the reader blocking queue after the last pop")


def enable(on=True):
    """Flip the observatory at runtime (tests, notebooks);
    ``FLAGS_step_profile`` only sets the import-time default."""
    global ENABLED
    ENABLED = bool(on)
    return ENABLED


def reset():
    """Drop the rings, the cost join and the regression baselines (test
    isolation; the executors re-join costs one-shot per executable, so a
    reset mid-run only re-prices on the next new executable)."""
    _dispatches.clear()
    with _lock:
        _records.clear()
        _cost.clear()
        _reg.clear()
    _inflight.clear()
    _tls.input_wait = 0.0


# -- reader-side starvation accounting ---------------------------------------

def note_input_wait(seconds, site="py_reader"):
    """Bank consumer-side reader wait against the CALLING thread's next
    step. Called by layers/io.py / reader/decorator.py under the
    ENABLED guard; monotonic durations, measured outside any lock."""
    _reader_wait.inc(seconds, site=site)
    _tls.input_wait = getattr(_tls, "input_wait", 0.0) + seconds


def note_queue_wait(seconds, depth, site="reader.queue"):
    """Queue-level pop accounting (BlockingQueue/NativeTensorQueue):
    wait seconds per site plus the post-pop depth gauge. NOT banked
    against a step — prefetch threads pop on their own clock; the
    per-step claim happens at the consumer (:func:`note_input_wait`)."""
    _reader_wait.inc(seconds, site=site)
    _queue_depth.set(depth)


# -- the per-step span -------------------------------------------------------

class StepSpan(object):
    """One step's open record. Executors hold one of these across
    every step and bracket each phase with enter()/exit(); ``finish``
    closes it into the dispatch ring and, with the observatory on, into
    the step ring. Plain slots — the per-step cost is this object plus
    a small dict."""

    __slots__ = ("origin", "t0", "phases", "input_wait", "fingerprint",
                 "_cur", "_t_cur", "_cost_cp", "_cost_avals",
                 "_cpu", "_cpu0", "_cpu_cur", "_out", "_out_cpu")

    def __init__(self, origin):
        self.origin = origin
        self.t0 = time.perf_counter()
        self.phases = {}
        self.input_wait = 0.0
        self.fingerprint = None
        self._cur = None
        self._t_cur = 0.0
        self._cost_cp = None
        self._cost_avals = None
        # with request tracing on (begin): {phase: the thread's CPU
        # seconds}, and its CPU clock at the first and the last bracket
        self._cpu = None
        self._cpu0 = self._cpu_cur = None
        # a caller's own work run inside the span (``outside``)
        self._out = self._out_cpu = 0.0

    def _account(self, phase):
        """Book the thread's CPU since the last bracket to ``phase``
        (None: to the residual). The first bracket follows ``begin`` at
        once, so its reading is the span's first."""
        now = time.thread_time()
        if phase is not None:
            self._cpu[phase] = (self._cpu.get(phase, 0.0)
                                + now - self._cpu_cur)
        elif self._cpu0 is None:
            self._cpu0 = now
        self._cpu_cur = now

    def enter(self, phase):
        """Open ``phase``; a phase still open closes on the same stamp,
        so two brackets that follow each other leave no gap between."""
        now = time.perf_counter()
        cur = self._cur
        if cur is not None:
            self.phases[cur] = self.phases.get(cur, 0.0) + (now - self._t_cur)
        if self._cpu is not None:
            self._account(cur)
        self._cur = phase
        self._t_cur = now
        _inflight[threading.get_ident()] = (self.origin, phase, now,
                                            self.t0)

    def exit(self):
        now = time.perf_counter()
        cur = self._cur
        if cur is not None:
            self.phases[cur] = self.phases.get(cur, 0.0) + (now - self._t_cur)
            if self._cpu is not None:
                self._account(cur)
            self._cur = None
            _inflight[threading.get_ident()] = (self.origin, "host", now,
                                                self.t0)

    def outside(self, fn):
        """Run ``fn()``, a caller's own host work, BETWEEN two brackets:
        its seconds (and the thread's CPU in them) stay in the span's
        wall and are kept out of every phase, the residual ``host``
        too, so the phases go on saying what the dispatch itself cost."""
        t0 = time.perf_counter()
        cpu0 = time.thread_time() if self._cpu is not None else 0.0
        try:
            fn()
        finally:
            self._out += time.perf_counter() - t0
            if self._cpu is not None:
                self._out_cpu += time.thread_time() - cpu0

    def pre_dispatch(self, cp, state, feeds, key, program=None):
        """Stamp the executable fingerprint and — one-shot per
        executable — snapshot avals for the deferred cost-model join.
        Must run BEFORE dispatch: the step call donates the mutable
        state buffers, after which their shapes are gone."""
        from paddle_tpu.observability import telemetry as _telemetry

        self.fingerprint = _telemetry.executable_fingerprint(cp, program)
        if getattr(cp, "_stepprof_cost_done", False):
            return
        cp._stepprof_cost_done = True
        try:
            self._cost_avals = _telemetry.step_avals(cp, state, feeds, key)
            self._cost_cp = cp
        except Exception:
            self._cost_avals = None


def begin(origin):
    """Open a span for one step and, with the observatory on, claim the
    calling thread's banked input wait (the readers bank it under the
    same flag). With request tracing on the span also keeps the thread's
    CPU seconds by phase. Executors call this on every step."""
    sp = StepSpan(origin)
    if _tracing.ENABLED:
        sp._cpu = {}
    if ENABLED:
        banked = getattr(_tls, "input_wait", 0.0)
        if banked:
            sp.input_wait = banked
            _tls.input_wait = 0.0
    return sp


class _DropOnError(object):
    """Shell around a bracketed dispatch: only ``finish`` pops the
    thread's in-flight entry, so a dispatch that raises (nan blame, OOM,
    a chaos fault) would leave :func:`inflight` a phase that stalls for
    ever. Stateless: one shared instance."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            _inflight.pop(threading.get_ident(), None)
        return False


DROP_ON_ERROR = _DropOnError()


def device_annotation():
    """The device-phase bracket on the profiler's clock: free when no
    profiler session is live, and in anyone's session the wait shows in
    the host plane beside the program's other spans."""
    return TraceAnnotation(_tracing.ANNOTATION_PREFIX + "device")


# -- cost-model join ---------------------------------------------------------

def _join_cost(sp, steps):
    """Price the executable with the hlo_cost_model fused-group table
    (one-shot per fingerprint; runs in ``finish``, off the timed path).
    Stores PER-STEP numbers — multi-step scans divide by the scan
    length so a 32-step dispatch prices like 32 single steps."""
    fp = sp.fingerprint
    cp, avals = sp._cost_cp, sp._cost_avals
    sp._cost_cp = sp._cost_avals = None
    if not fp or fp in _cost or cp is None or avals is None:
        return
    entry = None
    try:
        import jax

        from paddle_tpu.observability import _cost_model

        mod = _cost_model.load()
        closed = jax.make_jaxpr(cp.jitted)(*avals)
        jaxpr = closed.jaxpr
        while (len(jaxpr.eqns) == 1
               and jaxpr.eqns[0].primitive.name in ("pjit", "jit")):
            inner = jaxpr.eqns[0].params.get("jaxpr")
            if inner is None:
                break
            jaxpr = getattr(inner, "jaxpr", inner)
        opt = mod.optimize_jaxpr(jaxpr)
        groups = mod.analyze(opt)
        flops = float(sum(g.flops for g in groups))
        hbm = float(sum(g.bytes_total() for g in groups))
        k = float(max(1, steps))
        entry = {
            "flops": flops / k,
            "hbm_bytes": hbm / k,
            "groups": len(groups),
            # the roofline needs THIS device's published peaks; a
            # device the chip table does not know gets none
            "roofline_s": None,
            "bound": None,
        }
        from paddle_tpu.observability import telemetry as _telemetry

        peaks = _telemetry.chip_peaks()
        if peaks and peaks.hbm_bytes_per_sec:
            # roofline-predicted step time: each fused group pays
            # max(compute, HBM) — the cost model's pricing rule
            entry["roofline_s"] = sum(
                max(g.flops / peaks.bf16_flops,
                    g.bytes_total() / peaks.hbm_bytes_per_sec)
                for g in groups) / k
            entry["bound"] = (
                "hbm" if hbm / peaks.hbm_bytes_per_sec
                > flops / peaks.bf16_flops else "mxu")
    except Exception:
        entry = None
    with _lock:
        _cost.setdefault(fp, entry)


def cost_table():
    """The per-executable cost join (tests, step_breakdown)."""
    with _lock:
        return {k: (dict(v) if v else None) for k, v in _cost.items()}


# -- regression detector -----------------------------------------------------

def _median(vals):
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def _detect_regression(key, step_s, per_step_phases):
    """Rolling median+MAD excursion/drift detector. Called under _lock.
    Healthy steps extend the baseline; excursions do not (one slow step
    must not drag the median up), but _DRIFT_N consecutive excursions
    are accepted as a new regime: one 'drift' event, then rebase."""
    st = _reg.get(key)
    if st is None:
        st = {"window": collections.deque(maxlen=_REG_WINDOW),
              "phases": {}, "streak": 0}
        _reg[key] = st
    window = st["window"]
    verdict = None
    if len(window) >= _REG_MIN:
        med = _median(window)
        mad = _median([abs(x - med) for x in window])
        thresh = med + max(_REG_K * 1.4826 * mad, _REG_REL_FLOOR * med)
        if step_s > thresh:
            # the guilty phase: largest absolute excess over its own
            # rolling median
            guilty, excess, guilty_s, guilty_med = "host", 0.0, 0.0, 0.0
            for ph, cur in per_step_phases.items():
                base = st["phases"].get(ph)
                pmed = _median(base) if base else 0.0
                if cur - pmed > excess:
                    guilty, excess = ph, cur - pmed
                    guilty_s, guilty_med = cur, pmed
            st["streak"] += 1
            kind = "drift" if st["streak"] >= _DRIFT_N else "excursion"
            verdict = {
                "kind": kind, "phase": guilty,
                "step_s": step_s, "median_s": med, "threshold_s": thresh,
                "phase_s": guilty_s, "phase_median_s": guilty_med,
            }
            if kind == "drift":
                # sustained: accept the new regime so the detector does
                # not alarm on every step forever
                window.clear()
                st["phases"].clear()
                st["streak"] = 0
                window.append(step_s)
            return verdict
    st["streak"] = 0
    window.append(step_s)
    for ph, cur in per_step_phases.items():
        dq = st["phases"].get(ph)
        if dq is None:
            dq = st["phases"][ph] = collections.deque(maxlen=_REG_WINDOW)
        dq.append(cur)
    return verdict


# -- closing a span ----------------------------------------------------------

def finish(sp, steps=1, feeds=None, fetches=None, dispatch_only=False):
    """Close a span: always into the dispatch ring (the phases with
    the residual ``host``); with the observatory on also into a
    phase-attributed record: the cost-model join, achieved-MFU,
    boundedness verdict, regression detection, ring append + metric
    writes (returned; None when off). Runs entirely after the step's
    timed region — ``feeds``/``fetches`` are passed as containers (not
    pre-summed byte counts) so the wall clock stops on the FIRST line
    here, before any accounting arithmetic."""
    cpu = sp._cpu
    # the thread's last reading lies inside the wall, like the brackets'
    cpu_end = time.thread_time() if cpu is not None else None
    now = time.perf_counter()
    if sp._cur is not None:
        sp.exit()
    wall = now - sp.t0
    measured = sum(sp.phases.values())
    host = max(0.0, wall - measured - sp._out)
    phases = dict(sp.phases)
    phases["host"] = host
    if cpu is not None:
        # the residual's: the whole span's (from its first bracket to
        # here, or to a phase this call closed) less the brackets'
        first = cpu_end if sp._cpu0 is None else sp._cpu0
        cpu["host"] = (max(cpu_end, sp._cpu_cur or 0.0) - first
                       - sum(cpu.values()) - sp._out_cpu)
    _dispatches.append((sp.origin, time.time(), wall, phases, cpu))
    _inflight.pop(threading.get_ident(), None)
    if not ENABLED:
        return None
    steps = max(1, int(steps))
    feed_bytes = (sum(getattr(a, "nbytes", 0) for a in feeds.values())
                  if feeds else 0)
    fetch_bytes = (sum(getattr(f, "nbytes", 0) for f in fetches)
                   if fetches else 0)
    step_wall = wall + sp.input_wait
    phases = dict(phases)
    if sp.input_wait:
        phases["input_wait"] = sp.input_wait
    # coverage: every explicitly measured second (brackets + source-side
    # input wait) over the step's full wall — the ≥0.95 CI gate
    coverage = ((measured + sp._out + sp.input_wait) / step_wall
                if step_wall > 0 else 1.0)
    starvation = sp.input_wait / step_wall if step_wall > 0 else 0.0
    step_s = step_wall / steps

    _join_cost(sp, steps)
    cost = _cost.get(sp.fingerprint) if sp.fingerprint else None

    rec = {
        "ts": time.time(),
        "origin": sp.origin,
        "fingerprint": sp.fingerprint,
        "steps": steps,
        "wall_s": wall,
        "step_s": step_s,
        "phases": {p: v for p, v in phases.items() if v > 0.0},
        "coverage": coverage,
        "starvation_fraction": starvation,
        "feed_bytes": int(feed_bytes),
        "fetch_bytes": int(fetch_bytes),
    }
    if dispatch_only:
        # async handles: the span measures host dispatch latency, not a
        # step — excluded from MFU, starvation and the detector
        rec["dispatch_only"] = True
    achieved_mfu = None
    if cost and step_s > 0 and not dispatch_only:
        from paddle_tpu.observability import telemetry as _telemetry

        achieved = cost["flops"] / step_s
        rec["flops_per_step"] = cost["flops"]
        rec["hbm_bytes_per_step"] = cost["hbm_bytes"]
        rec["achieved_flops_per_sec"] = achieved
        # peak: an explicit FLAGS_peak_tflops, then the chip table. A
        # device the table does not know (the CPU test backend) has no
        # MFU and no roofline: not measured, never another chip's
        peak = _telemetry.peak_flops()
        rec["achieved_mfu"] = achieved_mfu = (
            achieved / peak if peak else None)
        rec["roofline_s"] = cost["roofline_s"]
        rec["predicted_ratio"] = (step_s / cost["roofline_s"]
                                  if cost["roofline_s"] else None)
    rec["bound"] = _classify(phases, sp.input_wait, cost)

    verdict = None
    if not dispatch_only:
        per_step_phases = {p: v / steps for p, v in phases.items()}
        with _lock:
            verdict = _detect_regression(sp.fingerprint or sp.origin,
                                         step_s, per_step_phases)
            if verdict:
                rec["regression"] = dict(verdict)
            _records.append(rec)
    else:
        with _lock:
            _records.append(rec)

    # metric writes outside the ring lock (each metric has its own)
    for p, v in rec["phases"].items():
        _phase_seconds.observe(v / steps, phase=p)
    if not dispatch_only:
        _starvation.set(starvation)
        if achieved_mfu is not None:
            _achieved_mfu.set(achieved_mfu)
    if verdict:
        _regressions.inc(1, kind=verdict["kind"], phase=verdict["phase"])
        from paddle_tpu.observability import blackbox as _blackbox

        # direct record() — regressions are rare and exactly what the
        # flight recorder exists for, so they land even when blackbox's
        # exception hooks are not armed. The verdict's own "kind"
        # (spike/drift) must not collide with record()'s event kind.
        fields = dict(verdict)
        fields["regression"] = fields.pop("kind")
        _blackbox.record(
            "step_regression", origin=sp.origin,
            fingerprint=(sp.fingerprint or "")[:16], **fields)
    return rec


def _classify(phases, input_wait, cost):
    """The step's boundedness verdict: ``input`` when starvation
    dominates, ``host`` when host-side phases outweigh device time,
    else the cost model's compute/bandwidth call (``device`` when the
    executable was never priced or the device has no published peaks)."""
    device_s = phases.get("device", 0.0)
    host_s = sum(v for p, v in phases.items()
                 if p not in ("device", "input_wait"))
    if input_wait >= max(device_s, host_s) and input_wait > 0:
        return "input"
    if host_s > device_s:
        return "host"
    if cost and cost["bound"]:
        return "compute" if cost["bound"] == "mxu" else "bandwidth"
    return "device"


# -- introspection + export --------------------------------------------------

def records():
    """Snapshot of the ring (oldest first)."""
    with _lock:
        return [dict(r) for r in _records]


def dispatch_records(origin=None):
    """Every dispatch the ring still holds, oldest first, flag or no
    flag: ``{"origin", "t1", "wall_s", "phases"}`` each. ``t1`` is the
    dispatch's end on ``time.time()`` (the clock of
    ``observability/tracing.py``'s spans, so the dispatches of a round
    can be picked out); ``phases`` holds seconds by phase, the residual
    ``host`` included; ``cpu`` the seconds the calling thread ran in each
    of those phases, None for a dispatch made with request tracing off.
    ``origin`` keeps one entry point's: ``single``, ``async``,
    ``multi_step``, ``parallel``."""
    return [{"origin": o, "t1": t1, "wall_s": wall, "phases": dict(ph),
             "cpu": None if cpu is None else dict(cpu)}
            for o, t1, wall, ph, cpu in _tracing.ring_snapshot(_dispatches)
            if origin is None or o == origin]


def inflight():
    """The current in-flight step bracket per thread — the watchdog's
    'which phase is stalled' answer. Lock-free reads of the _inflight
    dict: safe from signal handlers and the watchdog thread."""
    now = time.perf_counter()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, ent in list(_inflight.items()):
        origin, phase, t_phase, t_step = ent
        out.append({
            "thread": names.get(tid, str(tid)),
            "origin": origin,
            "phase": phase,
            "phase_age_s": round(now - t_phase, 3),
            "step_age_s": round(now - t_step, 3),
        })
    return out


def write_stepprof_jsonl(path, mode="w"):
    """One JSON line per profiled step — the file
    tools/step_breakdown.py --steps consumes; returns the count written.
    telemetry.flush() writes it as ``<metrics_path>.stepprof.jsonl``."""
    import json

    recs = records()
    with open(path, mode) as f:
        for r in recs:
            f.write(json.dumps(r, sort_keys=True) + "\n")
    return len(recs)


def _init_from_flags():
    from paddle_tpu import flags

    try:
        enable(flags.get("step_profile"))
    except KeyError:  # pragma: no cover - flag table always has it
        pass


_init_from_flags()
