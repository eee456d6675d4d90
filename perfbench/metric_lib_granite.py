"""What the ``granite_`` per-layer metric readers share. The cell's records
go by the keys the other decoder-only cells give (``records["serve"]``,
``entries/ssd_decoder_frontend.py``), and ``metric_lib_glm``'s helpers for
the traced dispatches and ``metric_lib_solar``'s for the rounds' expert
counters are used as they are; here is what finds THIS model's kernels by
the names a device event carries and counts their least time
(``kernel_costs_granite.py``). A decode dispatch's record is ``(live
slots, resident rows)`` at its first step. A reader returns None when
there is nothing to read: no device trace (the CPU rehearsals), or a
program without the kernel, span or counter (the parent of the PR that
brought them)."""

from perfbench import kernel_costs_granite as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import metric_lib_solar as solar
from perfbench import trace_reduce

# kernels/ssd.py, selective_scan.py, grouped_matmul.py: the names of their
# pallas_calls, which a device event carries
UPDATE_KERNEL = "ssd_state_update"
CHUNK_KERNEL = "ssd_chunk_prefill"
CONV_KERNEL = "ssm_causal_conv"
CONV_STEP_KERNEL = "ssm_conv_step"
EXPERT_KERNEL = glm.EXPERT_KERNEL
SSM_KERNELS = (UPDATE_KERNEL, CHUNK_KERNEL, CONV_KERNEL, CONV_STEP_KERNEL)


def _geometry(records):
    cfg = records["config"]
    att = list(cfg["layer_types"]).count("attention")
    return (cfg, cfg["pool"]["tokens_per_dispatch"],
            cfg["num_hidden_layers"] - att)


def decode_dispatch_ms(records):
    return glm.module_ms(records, UPDATE_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, CHUNK_KERNEL)


# the rounds' ``experts_held_tokens`` over their ``experts_routed_tokens``,
# and the median of their ``experts_held_hit``
held_expert_token_share = solar.held_expert_token_share
experts_hit = solar.experts_hit
expert_time_share = glm.expert_time_share


def decode_hbm_roofline(records):
    """Least seconds to move what the traced decode dispatches had to
    move (``kernel_costs_granite.decode_step_bytes`` a token step: the
    weights of the experts HIT, the other weights, the live slots' state
    twice, the visible K/V rows), over the device time of the runs that
    hold the state update kernel."""
    runs = lib.module_runs(records, holding=UPDATE_KERNEL)
    calls = glm.decode_dispatches(records)
    hit = experts_hit(records) if runs and calls else None
    if hit is None:
        return None
    cfg, K, _m = _geometry(records)
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, live, rows + j * live, hit)
                    for j in range(K)) / bw for live, rows in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def state_update_roofline(records):
    secs = glm.kernel_seconds(records, UPDATE_KERNEL)
    if not secs:
        return None
    cfg, K, n_mamba = _geometry(records)
    needed = n_mamba * K * sum(
        costs.least_seconds(*costs.state_update(cfg, live),
                            records["peaks"])
        for live, _rows in glm.decode_dispatches(records))
    return glm.share(needed, secs)


def chunk_prefill_roofline(records):
    """The greater of the chunked recurrence's time at the matrix unit's
    peak and at the memory's, at the traced prompts' REAL tokens, over the
    kernel's time."""
    secs = glm.kernel_seconds(records, CHUNK_KERNEL)
    if not secs:
        return None
    cfg, _K, n_mamba = _geometry(records)
    needed = n_mamba * sum(
        costs.least_seconds(*costs.chunk_prefill(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def expert_matmul_roofline(records):
    """The grouped products' least time over the pairs that fell on held
    experts: the held share of the router's outputs of a dispatch's
    tokens, the experts hit by the rounds' own counts (decode) or all the
    held ones (a prefill dispatch's tokens)."""
    secs = glm.kernel_seconds(records, EXPERT_KERNEL)
    hit = experts_hit(records) if secs else None
    if hit is None:
        return None
    cfg, K, _m = _geometry(records)
    k = cfg["num_experts_per_tok"]
    held_share = cfg["num_local_experts"] / float(cfg["expert_shard"]["of"])
    needed = 0.0
    for live, _rows in glm.decode_dispatches(records):
        needed += K * costs.least_seconds(
            *costs.expert_matmuls(cfg, live * k * held_share, hit),
            records["peaks"])
    for _bucket, lengths in glm.prefill_dispatches(records):
        needed += costs.least_seconds(
            *costs.expert_matmuls(cfg, sum(lengths) * k * held_share,
                                  cfg["num_local_experts"]),
            records["peaks"])
    return glm.share(cfg["num_hidden_layers"] * needed, secs)


def ssm_time_share(records):
    """The Mamba-2 mechanism's share of the device's busy time: both
    convolutions, the chunked prefill and the one-token state update, by
    their kernels' names. NOT in it, because a device event carries its
    HLO instruction's name and no scope: the mixer's products (z, x | B |
    C, dt, out), the softplus, the running sums the chunked kernel is
    handed and the gated norm, which are fusions like any other layer's."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0] for k in SSM_KERNELS)
    return 100.0 * secs / tr["busy_s"] if secs else None


def state_bytes_share(records):
    """The per-slot arrays' bytes of all a decode token step must move, in
    the median round, from the program's counters: ``state_bytes_live``
    over ``decode_step_bytes`` of the round's ``state_slots_live``,
    ``kv_rows_visible`` and ``experts_held_hit``."""
    cfg = records["config"]

    def stat(rounds):
        shares = []
        for r in rounds:
            head = r["spans"][0]
            if not head.get("state_bytes_live"):
                continue
            whole = costs.decode_step_bytes(
                cfg, head["state_slots_live"], head["kv_rows_visible"],
                head.get("experts_held_hit", cfg["num_local_experts"]))
            shares.append(100.0 * head["state_bytes_live"] / whole)
        return lib.median(shares)

    return glm.read_rounds(records, stat)


def read_prefill_pad_share(records):
    """The bucket rows the prefill dispatches walked for nothing, and on
    an earlier line the chunks the recurrence walked and skipped."""
    from perfbench import harness, metric_lib_jamba

    def stat(rounds):
        walked = sum(solar._head_counter(rounds, "prefill_chunks"))
        padded = sum(solar._head_counter(rounds, "prefill_chunks_padded"))
        if not walked:
            return None
        harness.log("chunks of %d tokens the Mamba-2 prefill walked in the "
                    "window's rounds: %d for real tokens, %d of padding "
                    "skipped" % (costs.CHUNK, walked, padded))
        return metric_lib_jamba.prefill_pad_share(rounds)

    return glm.read_rounds(records, stat)
