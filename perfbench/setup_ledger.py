"""The set-up ledger: where ``setup_s`` goes INSIDE the program, read from
the program's own account (``docs/OBSERVABILITY.md``, "The set-up ledger")
and, for the counters, cut where ``trace_lower_s`` is cut: at the harness's
copy of ``exec_cache.stats()`` at the end of warm-up (``records["cache"]``).
This file holds the arithmetic of the three ``setup_`` readers and prints
the ledger they come from, once a traced run, before the first of them.

What the program keeps:

* ``records["cache"]["by_function"]``: ``{fun_name: [calls, trace_s,
  lower_s, backend_s]}``, the totals ``trace_seconds``, ``lower_seconds``
  and ``compile_seconds`` by the jitted function's name. An executable's
  own step is ``split_step`` (``multi`` for a multi-step one:
  ``core/lowering.py``), the IR builder's shape inference an operator
  ``infer_op_shapes`` (``framework.py``); the other names are jitted
  helpers (a kernel's wrapper under its own ``jax.jit``, a kernel's body),
  eager ``jnp`` calls and the benchmark's own reference and weights.
* ``explain.setup_spans()``: ``{"index", "name", "path", "t0", "t1",
  "parent"}``: a span a program family and member, around its building
  and, where the constructor makes it, its first run; in the Transformer's
  session under a root ``session.init``, in the decoder-only session
  roots themselves.
* ``explain.events()``: one ``fresh_compile`` event an executable, with
  ``seq``, ``label`` (the path of the span its program was built or first
  run under), ``ops`` and ``ts``. ``records["cache"]["trace_cache_misses"]``
  counts the events before the harness's copy, so a later one is a compile
  inside ramp, window or drain, and has a name.

A program without the table (the parent of the PR that brought it) reads
None everywhere, and so does a run with no device trace (the CPU
rehearsals, like every reader but ``trace_lower_s``): ``numbers`` is the
arithmetic, on any records. The trainer has no session: it reports the two
counters and no span. What this cannot see is said in PERF.md section 7:
the seconds BY EXECUTABLE (nothing brackets an executable's first call),
Mosaic's own lowering of a kernel, and the harness's own parts.
"""

from perfbench import harness

# the jitted step of a CompiledProgram / MultiStepProgram
STEP_FUNCTIONS = ("split_step", "multi")
SHAPE_FUNCTION = "infer_op_shapes"
CACHE_KEY = "_setup_ledger"
NAMES = ("setup_spans_s", "setup_step_trace_lower_s",
         "setup_shape_inference_s")


def program_account():
    """The program's spans and events as they stand now (after the
    drain); None where it keeps none."""
    from paddle_tpu.observability import explain

    spans = getattr(explain, "setup_spans", None)
    if spans is None:
        return None
    return {"spans": spans(), "events": explain.events()}


def length(span):
    return span["t1"] - span["t0"] if span["t1"] is not None else 0.0


def roots(spans):
    return [sp for sp in spans if sp["parent"] is None]


def self_times(spans):
    """{index: the span's length less its children's}."""
    own = {sp["index"]: length(sp) for sp in spans}
    for sp in spans:
        if sp["parent"] in own:
            own[sp["parent"]] -= length(sp)
    return own


def step_rows(table):
    return [row for name, row in table.items() if name in STEP_FUNCTIONS]


def numbers(cache, account):
    """{metric: value} from the harness's copy and the program's account;
    None where the copy holds no table."""
    table = (cache or {}).get("by_function")
    if table is None or account is None:
        return None
    got = {
        "setup_step_trace_lower_s":
            sum(row[1] + row[2] for row in step_rows(table)),
        "setup_shape_inference_s":
            table.get(SHAPE_FUNCTION, (0, 0.0, 0.0, 0.0))[1],
    }
    if account["spans"]:
        got["setup_spans_s"] = sum(
            length(sp) for sp in roots(account["spans"]))
    return got


# -- the printed ledger --------------------------------------------------------

def _top(table, count=12):
    return sorted(table.items(), key=lambda kv: -(kv[1][1] + kv[1][2]))[
        :count]


def log_ledger(records, cache, account, got):
    table = cache["by_function"]
    setup = records.get("setup") or {}
    harness.log("set-up ledger (seconds; the counters at the end of "
                "warm-up, the spans and events as the program holds them "
                "now):")
    total = [sum(row[col] for row in table.values()) for col in (1, 2, 3)]
    harness.log("  by jitted function, %d names: trace %.3f lower %.3f "
                "compile-or-load %.3f (the totals: %.3f / %.3f / %.3f); "
                "%.3f s of the lowering enclosed tracing that the trace "
                "total counts too" % (
                    len(table), total[0], total[1], total[2],
                    cache["trace_seconds"], cache["lower_seconds"],
                    cache["compile_seconds"],
                    cache.get("trace_in_lower_seconds", 0.0)))
    for name, (calls, trace, lower, backend) in _top(table):
        harness.log("    %-28s %6d traces  trace %8.3f  lower %8.3f  "
                    "compile-or-load %8.3f%s" % (
                        name, calls, trace, lower, backend,
                        "  <- the executables' own steps"
                        if name in STEP_FUNCTIONS else
                        "  <- the IR builder's shape inference, an op"
                        if name == SHAPE_FUNCTION else ""))
    spans, events = account["spans"], account["events"]
    own = self_times(spans)
    asked = {}
    for ev in events:
        asked.setdefault(ev.get("label"), []).append(ev)
    def row(sp, indent):
        mine = asked.get(sp["path"], [])
        harness.log("%s%-16s %8.3f  %d executable(s)%s" % (
            indent, sp["name"], length(sp), len(mine),
            "".join(" [#%d %s ops%s]" % (
                ev["seq"], ev["ops"],
                "" if ev["span"] is not None else ", first run later")
                for ev in mine)))

    harness.log("  set-up spans %.3f s (the harness's program_build %.3f, "
                "warmup_dispatches %.3f):" % (
                    sum(length(sp) for sp in roots(spans)),
                    setup.get("program_build", 0.0),
                    setup.get("warmup_dispatches", 0.0)))
    for root in roots(spans):
        under = [sp for sp in spans if sp["parent"] == root["index"]]
        if not under:
            row(root, "    ")
            continue
        harness.log("    %s %.3f s, %.3f under no child span:" % (
            root["path"], length(root), own[root["index"]]))
        for sp in under:
            row(sp, "      ")
    early = cache.get("trace_cache_misses", len(events))
    unnamed = [ev for ev in events if ev.get("label") is None]
    harness.log("  executables: %d asked for by the end of warm-up, %d of "
                "them under no name%s" % (
                    early, len([ev for ev in unnamed if ev["seq"] < early]),
                    "".join(" [#%d %s ops]" % (ev["seq"], ev["ops"])
                            for ev in unnamed if ev["seq"] < early)))
    for ev in events:
        if ev["seq"] >= early:
            harness.log("  ASKED FOR AFTER THE OPENING: #%d %s (%s ops), "
                        "changed %s" % (ev["seq"], ev.get("label"),
                                        ev["ops"], ev["changed"]))
    harness.log("  read: %s" % ", ".join(
        "%s %.3f" % (name, got[name]) for name in NAMES if name in got))


def read_all(records):
    """{metric: value} of a run with a device trace; None without one
    and on a program without the table. Made once a run and kept on
    ``records``; the first call prints the ledger."""
    if CACHE_KEY in records:
        return records[CACHE_KEY]
    got = None
    if records.get("trace") is not None:
        cache, account = records.get("cache"), program_account()
        got = numbers(cache, account)
        if got is not None:
            log_ledger(records, cache, account, got)
    records[CACHE_KEY] = got
    return got


def read(records, name):
    got = read_all(records)
    return None if got is None else got.get(name)
