"""What the ``glm_`` per-layer metric readers share: the cell's own
records (``records["serve"]``, ``entries/decoder_frontend.py``: the keys
the Transformer's serving cells give, so the host plane's readers that
were there read this cell too; ``host`` says what each call dispatched), the
dispatches the traced stretch saw, and the least time their kernels
needed (``kernel_costs_glm.py``). A reader returns None when there is
nothing to read: no device trace (the CPU rehearsals), or a program
without the kernel, span or counter."""

from perfbench import kernel_costs_glm as costs
from perfbench import metric_lib as lib
from perfbench import program_records as pr
from perfbench import trace_reduce

DECODE_KERNEL = "latent_paged_decode_attention"
PREFILL_KERNEL = "flash_attention_fwd"
EXPERT_KERNEL = "gmm"     # kernels/grouped_matmul.py GROUPED_KERNEL_NAME
# what of the expert op (ops/moe_ops.py) a device event's name tells:
# the grouped products (and their metadata kernel) and the sorts
EXPERT_OPS = (EXPERT_KERNEL, "sort")


def mine(records):
    return records.get("serve")


def traced(records, key):
    """The host's records of the calls that began in the traced stretch
    (the profiler runs from the window's opening)."""
    rec = mine(records)
    if not rec or not rec.get("traced_s"):
        return []
    return [c for a, _b, c in rec["host"][key] if 0.0 <= a < rec["traced_s"]]


def decode_dispatches(records):
    """[(live slots, resident rows at the first step)] of the traced
    decode dispatches."""
    return [c for c in traced(records, "step") if c[0]]


def prefill_dispatches(records):
    """[(bucket, [prompt lengths])] of the traced prefill dispatches."""
    return [p for c in traced(records, "admit") for p in c]


def module_ms(records, kernel):
    runs = lib.module_runs(records, holding=kernel)
    return lib.median([1e3 * m["seconds"] for m in runs])


def share(needed_s, measured_s):
    return 100.0 * needed_s / measured_s if needed_s and measured_s else None


def kernel_seconds(records, kernel):
    tr = records.get("trace")
    if not tr:
        return None
    secs, calls = trace_reduce.kernel_time(tr, kernel)
    return secs if calls else None


def geometry(records):
    cfg = records["config"]
    K = cfg["pool"]["tokens_per_dispatch"]
    n_moe = cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)
    return cfg, K, n_moe


def decode_hbm_roofline(records):
    """Least seconds to read what the traced decode dispatches had to
    read (weights once a token step, the live rows once), over the device
    time of the runs that hold the decode kernel."""
    runs = lib.module_runs(records, holding=DECODE_KERNEL)
    calls = decode_dispatches(records)
    if not runs or not calls:
        return None
    cfg, K, _n = geometry(records)
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, rows + j * live)
                    for j in range(K)) / bw for live, rows in calls]
    return share(sum(per_call) / len(per_call),
                 sum(m["seconds"] for m in runs) / len(runs))


def expert_matmul_roofline(records):
    secs = kernel_seconds(records, EXPERT_KERNEL)
    if not secs:
        return None
    cfg, K, n_moe = geometry(records)
    k = cfg["num_experts_per_tok"]
    pairs = [live * k for live, _rows in decode_dispatches(records)
             for _j in range(K)]
    pairs += [sum(lengths) * k
              for _bucket, lengths in prefill_dispatches(records)]
    needed = n_moe * sum(costs.least_seconds(
        *costs.expert_matmuls(cfg, p), records["peaks"]) for p in pairs)
    return share(needed, secs)


def latent_decode_attention_roofline(records):
    secs = kernel_seconds(records, DECODE_KERNEL)
    if not secs:
        return None
    cfg, K, _n = geometry(records)
    needed = cfg["num_hidden_layers"] * sum(
        costs.least_seconds(*costs.latent_decode_attention(
            cfg, rows + j * live, live), records["peaks"])
        for live, rows in decode_dispatches(records) for j in range(K))
    return share(needed, secs)


def prefill_attention_roofline(records):
    secs = kernel_seconds(records, PREFILL_KERNEL)
    if not secs:
        return None
    cfg = records["config"]
    needed = cfg["num_hidden_layers"] * sum(
        costs.least_seconds(*costs.prefill_attention(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in prefill_dispatches(records))
    return share(needed, secs)


def expert_time_share(records):
    """The routed experts' share of the device's busy time: the grouped
    products with their metadata kernel (``ragged-dot``) and the two sorts
    (the top-k and the sort by expert). A device event carries its HLO
    instruction's name and nothing of the scope it was traced under, so
    the routing's and the combine's elementwise fusions cannot be told
    from the block's other fusions and are not in this share: 2-5 us each
    beside 1.28 ms a grouped product (my chip run, PR 27)."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0] for k in EXPERT_OPS)
    return 100.0 * secs / tr["busy_s"] if secs else None


def window_rounds(records, rounds):
    """The rounds that began inside the measured window, as
    ``program_records.window_rounds`` gives them, but placed by the MEDIAN
    offset between the benchmark's stamp before each ``session.step()``
    and the program's ``step`` span inside it, one stamp in twenty allowed
    to lie off: here the worker waits for the interpreter lock three
    quarters of its host time, and where it loses the lock BETWEEN the two
    stamps of one step they lie milliseconds apart, which that placement
    (every stamp within 2 ms) answers by reading ramp and drain rounds as
    well (one traced run of four, my chip runs, PR 27)."""
    from perfbench import harness

    outside = [st[0] for st in
               ((records.get("serve") or {}).get("host") or {}).get("step")
               or []]
    inside = [sp["t0"] for r in rounds
              for _i, sp in pr.named(r["spans"], "step")]
    n = len(outside)
    for k in range(len(inside) - n, -1, -1) if n else ():
        offsets = [inside[k + i] - outside[i] for i in range(n)]
        opening = sorted(offsets)[n // 2]
        if sum(abs(o - opening) >= pr.ALIGN_S for o in offsets) <= n // 20:
            closing = opening + records["serve"]["seconds"]
            return [r for r in rounds
                    if opening <= r["spans"][0]["t0"] < closing]
    harness.log("rounds: the benchmark's steps do not line up with the "
                "program's, so ramp and drain rounds are read as well")
    return rounds


def read_rounds(records, stat, *args, **kw):
    """``program_records.read_rounds`` over ``window_rounds`` above."""
    if not pr.traced_on_device(records):
        return None
    rounds = pr.program_rounds()
    if not rounds:
        return None
    rounds = window_rounds(records, rounds)
    if kw.get("log"):
        pr.log_rounds(records, rounds)
    return stat(rounds, *args)


def exec_host_ms_per_dispatch(records):
    """``program_records.read_serve_exec_host_ms`` over the same rounds:
    the mean host time of the executor dispatches between the window's
    first and last round, prefill (``single``) and decode
    (``multi_step``) alike."""
    if not pr.traced_on_device(records):
        return None
    rounds, dispatches = pr.program_rounds(), pr.program_dispatches()
    if not rounds or not dispatches:
        return None
    mine = pr.between_rounds(dispatches, window_rounds(records, rounds))
    for origin in sorted({d["origin"] for d in mine}):
        pr.log_dispatches("between the rounds, " + origin,
                          [d for d in mine if d["origin"] == origin])
    return pr.exec_host_ms_mean(mine)


# what of a round is no host work: the worker waits for requests, or for
# the device under a decode or a prefill dispatch
NOT_HOST = ("wait", "step.dispatch", "prefill.dispatch")


def round_host(rnd, key=pr.length):
    """A round less its waits and its dispatches, under ``key``."""
    spans = rnd["spans"]
    return key(spans[0]) - sum(key(sp) for name in NOT_HOST
                               for _i, sp in pr.named(spans, name))


def round_host_ms_p50(rounds):
    return pr.median_ms([round_host(r) for r in pr.dispatched(rounds)])


def worker_offcpu_share(rounds):
    """100 x (1 - cpu / wall) over the dispatching rounds' host time;
    None where the program took no CPU time on a span this needs."""
    mine = pr.dispatched(rounds)
    if not mine or any(sp["cpu"] is None for r in mine for sp in r["spans"]
                       if sp["name"] in ("round",) + NOT_HOST):
        return None
    wall = sum(round_host(r) for r in mine)
    cpu = sum(round_host(r, key=lambda sp: sp["cpu"]) for r in mine)
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None


def lay_idle(nodes, g0, g1, out):
    """Lay the idle gap [g0, g1) ns at the program's spans BY OVERLAP:
    what of it lies under a span goes to the span's children first and
    the rest to the span itself. Returns the ns that lay under any
    span. (``program_records.idle_by_span`` lays a whole gap at the one
    span that covers most of it, or at the bare round when none covers
    half: here the device's one gap a round runs from the decode
    dispatch's end through ``handoff``, ``enqueue`` and ``admit`` to the
    first prefill dispatch, under no single child.)"""
    covered = 0.0
    for name, t0, t1, kids in nodes:
        a, b = max(g0, t0), min(g1, t1)
        if b <= a:
            continue
        inner = lay_idle(kids, a, b, out)
        out[name] = out.get(name, 0.0) + (b - a - inner) / 1e9
        covered += b - a
    return covered


def idle_unattributed_share(records):
    """100 x chip 0's idle time under no program span, or under the bare
    ``round`` with no child open, over all of its idle time; the table is
    printed. None without a device trace or without the worker's rounds
    among the trace's ``pt:`` annotations."""
    from perfbench import harness

    if not pr.traced_on_device(records):
        return None
    path = pr.trace_file(records)
    worker = [events for events in (pr.program_threads(path) if path else [])
              if any(ev[0] == pr.ROUND for ev in events)]
    if not worker:
        return None
    forest = pr.nest(worker[0])
    gaps, _t0, _t1 = pr.chip0_idle(trace_reduce.flatten(path))
    by_span = {}
    for g0, g1 in gaps:
        under = lay_idle(forest, g0, g1, by_span)
        by_span[pr.UNATTRIBUTED] = (by_span.get(pr.UNATTRIBUTED, 0.0)
                                    + (g1 - g0 - under) / 1e9)
    harness.log("idle seconds of chip 0 by program span, each gap laid by "
                "overlap: %s" % ", ".join("%s %.4f" % kv for kv in sorted(
                    by_span.items(), key=lambda kv: -kv[1])))
    return pr.unattributed_share(by_span)


def prefill_prompts_per_dispatch_p50(rounds):
    per = [r["spans"][0]["prefill_prompts"]
           / float(r["spans"][0]["prefill_dispatches"])
           for r in rounds if r["spans"][0].get("prefill_dispatches")]
    return lib.median(per)
