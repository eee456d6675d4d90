"""What the ``longcat_`` per-layer metric readers share. The cell's records
go by the keys the other decoder-only cells give (``records["serve"]``,
``entries/shortcut_decoder_frontend.py``), so the host plane is read by
the ``glm_`` readers that were there (``BENCHMARK.json`` lists this cell
under them) and ``metric_lib_glm``'s helpers for the traced dispatches are
used as they are; here is what counts THIS model's least time
(``kernel_costs_longcat.py``): eight absorbed attentions a decode step over
two pools a layer, the flash forward at queries of 192 beside values of
128, the grouped products over the pairs that fell on HELD real experts
(a choice on an identity is no product). A decode dispatch's record is
``(live slots, resident rows a pool)`` at its first step. A reader
returns None when there is nothing to read: no device trace (the CPU
rehearsals), or a program without the kernel, span or counter (the parent
of the PR that brought them)."""

from perfbench import kernel_costs_longcat as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import metric_lib_glm52 as glm52
from perfbench import metric_lib_solar as solar
from perfbench import trace_reduce

DECODE_KERNEL = glm.DECODE_KERNEL      # latent_paged_decode_attention
PREFILL_KERNEL = glm.PREFILL_KERNEL    # flash_attention_fwd
EXPERT_KERNEL = glm.EXPERT_KERNEL      # gmm


def _geometry(records):
    cfg = records["config"]
    return cfg, cfg["pool"]["tokens_per_dispatch"], cfg["num_layers"]


def decode_dispatch_ms(records):
    return glm.module_ms(records, DECODE_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, PREFILL_KERNEL)


def zero_expert_choice_share(records):
    """The decode steps' (token, router output) choices that fell on a
    zero-compute (identity) expert over all of them: the rounds'
    ``experts_zero_tokens`` over their ``experts_routed_tokens``."""
    return glm52._share_of_rounds(records, "experts_zero_tokens",
                                  "experts_routed_tokens")


# the choices that fell on a real expert held here over all of them, and
# the held experts a step and layer that got a token (nothing where no
# round counts them): the counters the other held-shard cells read
held_expert_token_share = glm52.held_expert_token_share
experts_hit = solar.experts_hit
prefill_pad_share = glm52.prefill_pad_share


def _held_share(records):
    """The share of a token's choices that falls on a held real expert:
    the rounds' own counts where the program gives them, else the held
    share of the router's outputs."""
    read = held_expert_token_share(records)
    if read is not None:
        return read / 100.0
    cfg = records["config"]
    return cfg["n_routed_experts"] / float(
        cfg["expert_shard"]["of"] + cfg["zero_expert_num"])


def decode_hbm_roofline(records):
    """Least seconds to read what the traced decode dispatches had to
    read (the weights outside the routed experts once a token step, the
    weights of the held experts that got a token, the resident rows of
    all eight pools, the head), over the device time of the runs that
    hold the decode kernel."""
    runs = lib.module_runs(records, holding=DECODE_KERNEL)
    calls = glm.decode_dispatches(records)
    hit = experts_hit(records) if runs and calls else None
    if hit is None:
        return None
    cfg, K, _L = _geometry(records)
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, rows + j * live, hit)
                    for j in range(K)) / bw for live, rows in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def latent_decode_attention_roofline(records):
    """The absorbed kernel at 64 heads over a 128-wide value part, TWO
    calls a layer a token step (one a pool)."""
    secs = glm.kernel_seconds(records, DECODE_KERNEL)
    if not secs:
        return None
    cfg, K, L = _geometry(records)
    needed = 2 * L * sum(
        costs.least_seconds(*costs.latent_decode_attention(
            cfg, rows + j * live, live), records["peaks"])
        for live, rows in glm.decode_dispatches(records) for j in range(K))
    return glm.share(needed, secs)


def prefill_attention_roofline(records):
    """The flash forward at queries and keys of 192 beside values of 128,
    two calls a layer a prefill dispatch: its algorithm's operations."""
    secs = glm.kernel_seconds(records, PREFILL_KERNEL)
    if not secs:
        return None
    cfg, _K, L = _geometry(records)
    needed = 2 * L * sum(
        costs.least_seconds(*costs.prefill_attention(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def expert_matmul_roofline(records):
    """The grouped products' least time over the pairs that fell on held
    real experts: by the rounds' own counts (decode), by the same held
    share of a prefill dispatch's choices; every held expert read once a
    prefill dispatch, the experts a step's tokens hit once a step."""
    secs = glm.kernel_seconds(records, EXPERT_KERNEL)
    hit = experts_hit(records) if secs else None
    if hit is None:
        return None
    cfg, K, L = _geometry(records)
    k, held = cfg["moe_topk"], _held_share(records)
    needed = 0.0
    for live, _rows in glm.decode_dispatches(records):
        needed += K * costs.least_seconds(
            *costs.expert_matmuls(cfg, live * k * held, hit),
            records["peaks"])
    for _bucket, lengths in glm.prefill_dispatches(records):
        needed += costs.least_seconds(
            *costs.expert_matmuls(cfg, sum(lengths) * k * held,
                                  cfg["n_routed_experts"]),
            records["peaks"])
    return glm.share(L * needed, secs)


def expert_time_share(records):
    return glm.expert_time_share(records)


def attention_time_share(records):
    """Both attention kernels (absorbed decode, flash prefill) of the
    device's busy time. The projections around them are fusions like any
    other layer's and are not in it."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0]
               for k in (DECODE_KERNEL, PREFILL_KERNEL))
    return 100.0 * secs / tr["busy_s"] if secs else None
