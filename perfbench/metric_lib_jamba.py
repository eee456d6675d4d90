"""What the ``jamba_`` per-layer metric readers share. The cell's records
go by the keys the other serving cells give (``records["serve"]``,
``entries/hybrid_frontend.py``), so the host plane is read by the ``glm_``
readers that were there (``BENCHMARK.json`` lists this cell under them)
and ``metric_lib_glm``'s helpers for the traced dispatches are used as
they are; here is what finds THIS model's kernels by the names a device
event carries and counts their least time (``kernel_costs_jamba.py``). A
reader returns None when there is nothing to read: no device trace (the
CPU rehearsals), or a program without the kernel, span or counter."""

from perfbench import kernel_costs_jamba as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import program_records as pr
from perfbench import trace_reduce

# kernels/selective_scan.py and kernels/gqa_paged_attention.py: the names
# of their pallas_calls, which a device event carries
SCAN_KERNEL = "ssm_prefill_scan"
CONV_KERNEL = "ssm_causal_conv"
CONV_STEP_KERNEL = "ssm_conv_step"
UPDATE_KERNEL = "ssm_state_update"
GQA_KERNEL = "gqa_paged_decode_attention"
SSM_KERNELS = (SCAN_KERNEL, CONV_KERNEL, CONV_STEP_KERNEL, UPDATE_KERNEL)


def decode_dispatch_ms(records):
    return glm.module_ms(records, UPDATE_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, SCAN_KERNEL)


def _tokens_per_dispatch(records):
    return records["config"]["pool"]["tokens_per_dispatch"]


def decode_hbm_roofline(records):
    """Least seconds to move what the traced decode dispatches had to
    move (``kernel_costs_jamba.decode_step_bytes`` a token step), over the
    device time of the runs that hold the state update kernel."""
    runs = lib.module_runs(records, holding=UPDATE_KERNEL)
    calls = glm.decode_dispatches(records)
    if not runs or not calls:
        return None
    cfg, K = records["config"], _tokens_per_dispatch(records)
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, live, rows + j * live)
                    for j in range(K)) / bw for live, rows in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def _layers(records, kind):
    return costs.layer_kinds(records["config"]).count(kind)


def state_update_roofline(records):
    secs = glm.kernel_seconds(records, UPDATE_KERNEL)
    if not secs:
        return None
    cfg, K = records["config"], _tokens_per_dispatch(records)
    needed = _layers(records, "mamba") * K * sum(
        costs.least_seconds(*costs.state_update(cfg, live),
                            records["peaks"])
        for live, _rows in glm.decode_dispatches(records))
    return glm.share(needed, secs)


def prefill_scan_roofline(records):
    secs = glm.kernel_seconds(records, SCAN_KERNEL)
    if not secs:
        return None
    needed = _layers(records, "mamba") * sum(
        costs.least_seconds(*costs.prefill_scan(records["config"], lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def gqa_decode_attention_roofline(records):
    secs = glm.kernel_seconds(records, GQA_KERNEL)
    if not secs:
        return None
    cfg, K = records["config"], _tokens_per_dispatch(records)
    needed = _layers(records, "attention") * sum(
        costs.least_seconds(*costs.gqa_decode_attention(
            cfg, rows + j * live, live), records["peaks"])
        for live, rows in glm.decode_dispatches(records) for j in range(K))
    return glm.share(needed, secs)


def ssm_time_share(records):
    """The state-space mechanism's share of the device's busy time: the
    four kernels of ``kernels/selective_scan.py`` (both convolutions, the
    prefill scan, the one-token state update). NOT in it, because a device
    event carries its HLO instruction's name and no scope: the mixer's
    products (in_proj, x_proj, dt_proj, out_proj), the three inner norms
    with softplus, and the gate ``y * silu(z)``, which are fusions like
    any other layer's."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0] for k in SSM_KERNELS)
    return 100.0 * secs / tr["busy_s"] if secs else None


def prefill_pad_share(rounds):
    """100 x the rounds' ``prefill_pad_tokens`` over those and their
    ``prefill_tokens``: the bucket rows a prefill dispatch walks for
    nothing. None where the program does not count them."""
    heads = [r["spans"][0] for r in rounds]
    if not any("prefill_pad_tokens" in h for h in heads):
        return None
    pad = sum(h.get("prefill_pad_tokens", 0) for h in heads)
    real = sum(h.get("prefill_tokens", 0) for h in heads)
    return 100.0 * pad / (pad + real) if pad + real else None


def state_slots_live_p50(rounds):
    live = [r["spans"][0]["state_slots_live"] for r in rounds
            if "state_slots_live" in r["spans"][0]]
    return lib.median(live)


def read_prefill_pad_share(records):
    from perfbench import harness

    def stat(rounds):
        live = state_slots_live_p50(rounds)
        if live is not None:
            cfg = records["config"]
            harness.log(
                "state_slots_live at a decode dispatch, the median round: "
                "%d of %d slots, %.3f GB of recurrent state read and "
                "written a token step"
                % (live, cfg["pool"]["num_slots"],
                   2 * live * costs.state_bytes_per_slot(cfg) / 1e9))
        return prefill_pad_share(rounds)

    return glm.read_rounds(records, stat)
