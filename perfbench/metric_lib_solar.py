"""What the ``solar_`` per-layer metric readers share. The cell's records
go by the keys the other decoder-only cells give (``records["serve"]``,
``entries/linear_decoder_frontend.py``), so the host plane is read by the
``glm_`` readers that were there (``BENCHMARK.json`` lists this cell under
them) and ``metric_lib_glm``'s helpers for the traced dispatches are used
as they are; here is what finds THIS model's kernels by the names a device
event carries and counts their least time (``kernel_costs_solar.py``). A
decode dispatch's record is ``(live slots, resident rows)`` at its first
step. A reader returns None when there is nothing to read: no device trace
(the CPU rehearsals), or a program without the kernel, span or counter
(the parent of the PR that brought them)."""

from perfbench import kernel_costs_solar as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import metric_lib_glm52 as glm52
from perfbench import trace_reduce

# kernels/delta_rule.py, selective_scan.py, gqa_paged_attention.py,
# flash_attention.py, grouped_matmul.py: the names of their pallas_calls,
# which a device event carries
UPDATE_KERNEL = "delta_rule_state_update"
CHUNK_KERNEL = "delta_rule_chunk_prefill"
CONV_KERNEL = "ssm_causal_conv"
CONV_STEP_KERNEL = "ssm_conv_step"
GQA_KERNEL = "gqa_paged_decode_attention"
PREFILL_KERNEL = glm.PREFILL_KERNEL
EXPERT_KERNEL = glm.EXPERT_KERNEL
LINEAR_KERNELS = (UPDATE_KERNEL, CHUNK_KERNEL, CONV_KERNEL, CONV_STEP_KERNEL)


def _geometry(records):
    cfg = records["config"]
    gqa = len(cfg["gqa_layers"])
    return (cfg, cfg["pool"]["tokens_per_dispatch"], gqa,
            cfg["num_hidden_layers"] - gqa)


def decode_dispatch_ms(records):
    return glm.module_ms(records, UPDATE_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, CHUNK_KERNEL)


_head_counter = glm52._head_counter
# the rounds' ``experts_held_tokens`` over their ``experts_routed_tokens``
held_expert_token_share = glm52.held_expert_token_share


def experts_hit(records):
    """Held experts a step and layer that got a token: the median of the
    rounds' ``experts_held_hit``; nothing where no round counts them."""
    return glm.read_rounds(
        records, lambda rounds: lib.median(
            _head_counter(rounds, "experts_held_hit")))


def decode_hbm_roofline(records):
    """Least seconds to move what the traced decode dispatches had to
    move (``kernel_costs_solar.decode_step_bytes`` a token step: the
    weights of the experts HIT, the other weights, the live slots' state
    twice, the visible K/V rows), over the device time of the runs that
    hold the state update kernel."""
    runs = lib.module_runs(records, holding=UPDATE_KERNEL)
    calls = glm.decode_dispatches(records)
    hit = experts_hit(records) if runs and calls else None
    if hit is None:
        return None
    cfg, K, _g, _l = _geometry(records)
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, live, rows + j * live, hit)
                    for j in range(K)) / bw for live, rows in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def state_update_roofline(records):
    secs = glm.kernel_seconds(records, UPDATE_KERNEL)
    if not secs:
        return None
    cfg, K, _g, n_linear = _geometry(records)
    needed = n_linear * K * sum(
        costs.least_seconds(*costs.state_update(cfg, live),
                            records["peaks"])
        for live, _rows in glm.decode_dispatches(records))
    return glm.share(needed, secs)


def chunk_prefill_roofline(records):
    """The greater of the chunked delta rule's time at the matrix unit's
    peak and at the memory's, at the traced prompts' REAL tokens, over the
    kernel's time."""
    secs = glm.kernel_seconds(records, CHUNK_KERNEL)
    if not secs:
        return None
    cfg, _K, _g, n_linear = _geometry(records)
    needed = n_linear * sum(
        costs.least_seconds(*costs.chunk_prefill(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def gqa_decode_attention_roofline(records):
    secs = glm.kernel_seconds(records, GQA_KERNEL)
    if not secs:
        return None
    cfg, K, n_gqa, _l = _geometry(records)
    needed = n_gqa * sum(
        costs.least_seconds(*costs.gqa_decode_attention(
            cfg, rows + j * live, live), records["peaks"])
        for live, rows in glm.decode_dispatches(records) for j in range(K))
    return glm.share(needed, secs)


def prefill_attention_roofline(records):
    secs = glm.kernel_seconds(records, PREFILL_KERNEL)
    if not secs:
        return None
    cfg, _K, n_gqa, _l = _geometry(records)
    needed = n_gqa * sum(
        costs.least_seconds(*costs.prefill_attention(cfg, lengths),
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
    cfg, K, _g, _l = _geometry(records)
    k = cfg["num_experts_per_tok"]
    held_share = cfg["n_routed_experts"] / float(cfg["expert_shard"]["of"])
    needed = 0.0
    for live, _rows in glm.decode_dispatches(records):
        needed += K * costs.least_seconds(
            *costs.expert_matmuls(cfg, live * k * held_share, hit),
            records["peaks"])
    for _bucket, lengths in glm.prefill_dispatches(records):
        needed += costs.least_seconds(
            *costs.expert_matmuls(cfg, sum(lengths) * k * held_share,
                                  cfg["n_routed_experts"]),
            records["peaks"])
    return glm.share(cfg["num_hidden_layers"] * needed, secs)


def linear_time_share(records):
    """The linear-attention mechanism's share of the device's busy time:
    both convolutions, the chunked prefill and the one-token state update,
    by their kernels' names. NOT in it, because a device event carries its
    HLO instruction's name and no scope: the mixer's products (q | k | v,
    the low-rank gates, beta, o), the gates' softplus and sigmoid, the L2
    norms of a decode step and the gated head norm, which are fusions like
    any other layer's."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0] for k in LINEAR_KERNELS)
    return 100.0 * secs / tr["busy_s"] if secs else None


expert_time_share = glm.expert_time_share


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
            if "state_bytes_live" not in head or not head["state_bytes_live"]:
                continue
            whole = costs.decode_step_bytes(
                cfg, head["state_slots_live"], head["kv_rows_visible"],
                head.get("experts_held_hit", cfg["n_routed_experts"]))
            shares.append(100.0 * head["state_bytes_live"] / whole)
        return lib.median(shares)

    return glm.read_rounds(records, stat)


def read_prefill_pad_share(records):
    """The bucket rows the prefill dispatches walked for nothing, and on
    an earlier line the chunks the delta rule walked and skipped."""
    from perfbench import harness, metric_lib_jamba

    def stat(rounds):
        walked = sum(_head_counter(rounds, "prefill_chunks"))
        padded = sum(_head_counter(rounds, "prefill_chunks_padded"))
        if not walked:
            return None
        harness.log("chunks of %d tokens the delta rule's prefill walked in "
                    "the window's rounds: %d for real tokens, %d of padding "
                    "skipped" % (costs.CHUNK, walked, padded))
        return metric_lib_jamba.prefill_pad_share(rounds)

    return glm.read_rounds(records, stat)
