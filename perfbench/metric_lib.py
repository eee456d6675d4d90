"""What the per-layer metric readers share. A reader gets the run's
``records`` (see ``run.py``) and returns a number, or None when there is
nothing to read."""

from perfbench import kernel_costs, trace_reduce
from perfbench.loadgen import percentile

DECODE_KERNEL = "paged_decode_attention"
FLASH_FWD = "flash_attention_fwd"
FLASH_BWD = ("flash_attention_bwd_dkv", "flash_attention_bwd_dq")


def setup_seconds(records, *parts):
    got = [records["setup"][p] for p in parts if p in records["setup"]]
    return sum(got) if got else None


def idle_share(records):
    tr = records.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def module_runs(records, holding):
    """Runs of compiled programs in the trace that hold a kernel."""
    tr = records.get("trace")
    if not tr:
        return []
    return [m for m in tr["modules"]
            if any(holding in name for name in m["ops"])]


def median(values):
    return percentile(values, 50) if values else None


def decode_dispatch_ms(records):
    """Device time of one decode dispatch: the median length of the
    compiled-program runs that hold the paged decode kernel."""
    runs = module_runs(records, holding=DECODE_KERNEL)
    return median([1e3 * m["seconds"] for m in runs])


def train_step_ms(records):
    """Device time of one optimizer step: the median length of the runs
    that hold the flash backward kernel."""
    runs = module_runs(records, holding=FLASH_BWD[0])
    return median([1e3 * m["seconds"] for m in runs])


def decode_work(records):
    """What the traced decode dispatches were asked for, from the host's
    own count of live slots: (slot-steps, self-attention context tokens,
    cross-attention context tokens) over the traced stretch."""
    serve = records.get("serve")
    if not serve or not serve.get("traced_steps"):
        return None
    steps = serve["traced_steps"]          # [(live slots, tokens a slot)]
    slot_steps = sum(n * k for n, k in steps)
    w = serve["length_weights"]
    return (slot_steps, slot_steps * w["mean_self_context"],
            slot_steps * w["mean_cross_context"])


def attention_roofline(records, kernel, which, where=None):
    tr, work = records.get("trace"), decode_work(records)
    if not tr or not work:
        return None
    secs, calls = trace_reduce.kernel_time(tr, kernel, where)
    if not calls:
        return None
    cfg = records["config"]
    layers = cfg["n_layer"]
    slot_steps, self_ctx, cross_ctx = work
    ctx = self_ctx if which == "self" else cross_ctx
    ops, moved = kernel_costs.decode_attention(
        ctx * layers, slot_steps * layers, cfg["n_head"],
        cfg["d_model"] // cfg["n_head"], 4)
    share, _bound = kernel_costs.roofline_share(ops, moved, secs,
                                                records["peaks"])
    return share


def serve_percentile(records, key, q):
    serve = records.get("serve")
    if not serve or not serve["summary"].get(key):
        return None
    return percentile(serve["summary"][key], q)


def trace_stat_p50_ms(records, stat):
    """Median of one per-request stat of the program's request traces."""
    traces = (records.get("serve") or {}).get("traces") or []
    vals = [1e3 * t["stats"][stat] for t in traces
            if t["stats"].get(stat) is not None and t["stats"][stat] > 0]
    return median(vals)


def admits_per_s(records):
    serve = records.get("serve")
    if not serve:
        return None
    n = sum(k for a, _b, k in serve["host"]["admit"]
            if 0.0 <= a < serve["seconds"])
    return n / serve["seconds"] if n else None


def dispatch_gap_p50_ms(records):
    serve = records.get("serve")
    if not serve:
        return None
    steps = [s for s in serve["host"]["step"] if 0.0 <= s[0] < serve["seconds"]]
    gaps = [1e3 * (b[0] - a[1]) for a, b in zip(steps, steps[1:])]
    return median(gaps)
