"""The per-layer readers of the ``kimi_linear_5l`` cell, as FUNCTIONS: no
entry of ``BENCHMARK.json`` names them yet (``per_layer`` held its most,
128 entries, before the PR that brought them, and a PR may only add: a
``benchmark`` PR that makes room appends an entry and a one-line
``layer_metrics/kimi_<reader>.py`` each; ``tests/perfbench`` holds them to
hand counts meanwhile). The cell's records go
by the keys the other decoder-only cells give (``records["serve"]``,
``entries/linear_latent_decoder_frontend.py``), so the host plane is read
by the ``glm_`` readers that were there (``BENCHMARK.json`` lists this
cell under them), ``metric_lib_glm``'s helpers for the traced dispatches
are used as they are, and the readers that read no key of a configuration
(the linear mechanism's time share, the prefill's padding, the held
experts' share of the choices) are ``metric_lib_solar``'s and
``metric_lib_glm52``'s themselves. No kernel is new: here is what finds
THIS model's kernels by the names a device event carries (the delta
rule's and the convolution's of ``metric_lib_solar``, the absorbed decode,
the flash forward and the grouped products of ``metric_lib_glm``) and
counts their least time at this configuration's keys
(``kernel_costs_kimi.py``). A decode dispatch's record is ``(live slots,
resident rows)`` at its first step. A reader returns None when there is
nothing to read: no device trace (the CPU rehearsals), or a program
without the kernel, span or counter."""

from perfbench import kernel_costs_kimi as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import metric_lib_glm52 as glm52
from perfbench import metric_lib_solar as solar
from perfbench import trace_reduce

# kernels/delta_rule.py, latent_attention.py, flash_attention.py,
# grouped_matmul.py: the names of their pallas_calls
UPDATE_KERNEL = solar.UPDATE_KERNEL
CHUNK_KERNEL = solar.CHUNK_KERNEL
DECODE_KERNEL = glm.DECODE_KERNEL
PREFILL_KERNEL = glm.PREFILL_KERNEL
EXPERT_KERNEL = glm.EXPERT_KERNEL


def _geometry(records):
    """(configuration, token steps a dispatch, latent layers, linear
    layers, expert layers)."""
    cfg = records["config"]
    latent = len(costs.latent_layers(cfg))
    L = cfg["num_hidden_layers"]
    return (cfg, cfg["pool"]["tokens_per_dispatch"], latent, L - latent,
            L - cfg["first_k_dense_replace"])


def decode_dispatch_ms(records):
    return glm.module_ms(records, UPDATE_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, CHUNK_KERNEL)


experts_hit = solar.experts_hit
held_expert_token_share = glm52.held_expert_token_share
linear_time_share = solar.linear_time_share
expert_time_share = glm.expert_time_share
read_prefill_pad_share = solar.read_prefill_pad_share


def decode_hbm_roofline(records):
    """Least seconds to move what the traced decode dispatches had to
    move (``kernel_costs_kimi.decode_step_bytes`` a token step: the
    weights of the experts HIT, the other weights, the live slots' state
    twice, the resident latent rows), over the device time of the runs
    that hold the state update kernel."""
    runs = lib.module_runs(records, holding=UPDATE_KERNEL)
    calls = glm.decode_dispatches(records)
    hit = experts_hit(records) if runs and calls else None
    if hit is None:
        return None
    cfg, K = _geometry(records)[:2]
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, live, rows + j * live, hit)
                    for j in range(K)) / bw for live, rows in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def state_update_roofline(records):
    """``delta_rule_state_update``: one call a linear layer a token."""
    secs = glm.kernel_seconds(records, UPDATE_KERNEL)
    if not secs:
        return None
    cfg, K, _lat, n_linear, _moe = _geometry(records)
    needed = n_linear * K * sum(
        costs.least_seconds(*costs.state_update(cfg, live),
                            records["peaks"])
        for live, _rows in glm.decode_dispatches(records))
    return glm.share(needed, secs)


def chunk_prefill_roofline(records):
    """``delta_rule_chunk_prefill`` at the traced prompts' REAL tokens:
    one call a linear layer a prefill dispatch."""
    secs = glm.kernel_seconds(records, CHUNK_KERNEL)
    if not secs:
        return None
    cfg, _K, _lat, n_linear, _moe = _geometry(records)
    needed = n_linear * sum(
        costs.least_seconds(*costs.chunk_prefill(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def latent_decode_attention_roofline(records):
    """``latent_paged_decode_attention`` at 32 heads: one call a latent
    layer a token step."""
    secs = glm.kernel_seconds(records, DECODE_KERNEL)
    if not secs:
        return None
    cfg, K, n_latent, _lin, _moe = _geometry(records)
    needed = n_latent * sum(
        costs.least_seconds(*costs.latent_decode_attention(
            cfg, rows + j * live, live), records["peaks"])
        for live, rows in glm.decode_dispatches(records) for j in range(K))
    return glm.share(needed, secs)


def prefill_attention_roofline(records):
    """``flash_attention_fwd`` at queries and keys of 192 beside values of
    128: one call a latent layer a prefill dispatch."""
    secs = glm.kernel_seconds(records, PREFILL_KERNEL)
    if not secs:
        return None
    cfg, _K, n_latent, _lin, _moe = _geometry(records)
    needed = n_latent * sum(
        costs.least_seconds(*costs.prefill_attention(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def expert_matmul_roofline(records):
    """``gmm``: the grouped products' least time over the pairs that fell
    on held experts: the held share of the router's outputs of a
    dispatch's tokens, the experts hit by the rounds' own counts (decode)
    or all the held ones (a prefill dispatch's tokens), in each of the
    expert layers."""
    secs = glm.kernel_seconds(records, EXPERT_KERNEL)
    hit = experts_hit(records) if secs else None
    if hit is None:
        return None
    cfg, K, _lat, _lin, n_moe = _geometry(records)
    k = cfg["num_experts_per_token"]
    held_share = cfg["num_experts"] / float(cfg["expert_shard"]["of"])
    needed = 0.0
    for live, _rows in glm.decode_dispatches(records):
        needed += K * costs.least_seconds(
            *costs.expert_matmuls(cfg, live * k * held_share, hit),
            records["peaks"])
    for _bucket, lengths in glm.prefill_dispatches(records):
        needed += costs.least_seconds(
            *costs.expert_matmuls(cfg, sum(lengths) * k * held_share,
                                  cfg["num_experts"]),
            records["peaks"])
    return glm.share(n_moe * needed, secs)


def latent_time_share(records):
    """The latent layer's two kernels' share of the device's busy time:
    the absorbed decode and the flash forward (this model's only user of
    it). NOT in it, as in ``metric_lib_solar.linear_time_share``: the
    layer's products (q, kv_a, kv_b absorbed into the query and applied to
    the output, o), which are fusions like any other layer's."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0]
               for k in (DECODE_KERNEL, PREFILL_KERNEL))
    return 100.0 * secs / tr["busy_s"] if secs else None


def _bytes_share(records, part):
    """``part`` of ``kernel_costs_kimi.decode_step_parts`` over their sum,
    in the median round, from the program's counters
    (``state_slots_live``, ``kv_rows_visible``, ``experts_held_hit``) and
    the row as the pool holds it (``geometry["latent_row_bytes"]``)."""
    cfg = records["config"]
    row_bytes = (glm.mine(records) or {}).get("geometry", {}).get(
        "latent_row_bytes")

    def stat(rounds):
        shares = []
        for r in rounds:
            head = r["spans"][0]
            if not head.get("state_slots_live"):
                continue
            parts = costs.decode_step_parts(
                cfg, head["state_slots_live"], head["kv_rows_visible"],
                head.get("experts_held_hit", cfg["num_experts"]),
                row_bytes)
            shares.append(100.0 * parts[part] / sum(parts.values()))
        return lib.median(shares)

    return glm.read_rounds(records, stat) if row_bytes else None


def state_bytes_share(records):
    return _bytes_share(records, "state")


def latent_bytes_share(records):
    return _bytes_share(records, "latent")
