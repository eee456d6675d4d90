"""What the ``glm52_`` per-layer metric readers share. The cell's records
go by the keys the other decoder-only cells give (``records["serve"]``,
``entries/sparse_decoder_frontend.py``), so the host plane is read by the
``glm_`` readers that were there (``BENCHMARK.json`` lists this cell under
them) and ``metric_lib_glm``'s helpers for the traced dispatches are used
as they are; here is what finds THIS model's kernels and composed device
paths by what a device event carries (an HLO instruction's name and its
first result's shape) and counts their least time
(``kernel_costs_glm52.py``). A decode dispatch's record is ``(live slots,
resident rows, selected rows)`` at its first step. A reader returns None
when there is nothing to read: no device trace (the CPU rehearsals), or a
program without the kernel, span or counter (the parent of the PR that
brought them)."""

from perfbench import kernel_costs_glm52 as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import trace_reduce

# kernels/sparse_latent_attention.py, grouped_matmul.py: the names of
# their pallas_calls, which a device event carries
SCORE_KERNEL = "index_score_decode"
DECODE_KERNEL = "sparse_latent_decode_attention"
PREFILL_KERNEL = "sparse_latent_prefill_attention"
EXPERT_KERNEL = glm.EXPERT_KERNEL
_KERNELS = (SCORE_KERNEL, DECODE_KERNEL, PREFILL_KERNEL)
_QUERY_BLOCK = 128    # kernels/sparse_latent_attention.py _QUERY_BLOCK


def _geometry(records):
    cfg = records["config"]
    return (cfg, cfg["pool"]["tokens_per_dispatch"],
            cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0),
            list(cfg["indexer_types"]).count("full"))


def decode_dispatch_ms(records):
    return glm.module_ms(records, DECODE_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, PREFILL_KERNEL)


def _head_counter(rounds, key):
    return [r["spans"][0][key] for r in rounds if key in r["spans"][0]]


def experts_hit(records):
    """Held experts a step and layer that got a token: the median of the
    rounds' ``experts_held_hit``, else what an even spread of the median
    dispatch's choices gives."""
    read = glm.read_rounds(
        records, lambda rounds: lib.median(
            _head_counter(rounds, "experts_held_hit")))
    if read is not None:
        return read
    calls = glm.decode_dispatches(records)
    if not calls:
        return None
    cfg = records["config"]
    return costs.expected_experts_hit(
        cfg, lib.median([c[0] for c in calls]) * cfg["num_experts_per_tok"])


def decode_hbm_roofline(records):
    """Least seconds to read what the traced decode dispatches had to
    read (the weights outside the routed experts once a token step, the
    weights of the held experts that got a token, the selected latent rows
    and every resident narrow key), over the device time of the runs that
    hold the sparse decode kernel."""
    runs = lib.module_runs(records, holding=DECODE_KERNEL)
    calls = glm.decode_dispatches(records)
    hit = experts_hit(records) if runs and calls else None
    if hit is None:
        return None
    cfg, K, _m, _f = _geometry(records)
    topk = cfg["index_topk"]
    bw = records["peaks"]["hbm_bytes_per_s"]
    # a later step of the dispatch holds a row more a slot, and selects
    # one more where the slot is still under index_topk: counted as held
    per_call = [sum(costs.decode_step_bytes(
        cfg, min(sel + j * live, live * topk), rows + j * live, hit)
        for j in range(K)) / bw for live, rows, sel in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def index_score_decode_roofline(records):
    secs = glm.kernel_seconds(records, SCORE_KERNEL)
    if not secs:
        return None
    cfg, K, _m, n_full = _geometry(records)
    needed = n_full * sum(
        costs.least_seconds(*costs.index_score_decode(
            cfg, rows + j * live, live), records["peaks"])
        for live, rows, _sel in glm.decode_dispatches(records)
        for j in range(K))
    return glm.share(needed, secs)


def sparse_decode_attention_roofline(records):
    """The selected rows' least time over the time of the COMPOSED path
    that reads them: the gather of the chosen rows from the paged pool
    with its page arithmetic (by their results' shapes) and the kernel
    that streams the gathered rows. A kernel that fetched the chosen rows
    itself would raise this share; the kernel's own time alone would
    lower it."""
    secs = glm.kernel_seconds(records, DECODE_KERNEL)
    if not secs:
        return None
    cfg, K, _m, _f = _geometry(records)
    topk = cfg["index_topk"]
    secs += _shaped_seconds(records["trace"], _gather_ops(cfg))
    needed = cfg["num_hidden_layers"] * sum(
        costs.least_seconds(*costs.sparse_decode_attention(
            cfg, min(sel + j * live, live * topk), live), records["peaks"])
        for live, _rows, sel in glm.decode_dispatches(records)
        for j in range(K))
    return glm.share(needed, secs)


def prefill_attention_roofline(records):
    secs = glm.kernel_seconds(records, PREFILL_KERNEL)
    if not secs:
        return None
    cfg = records["config"]
    needed = cfg["num_hidden_layers"] * sum(
        costs.least_seconds(*costs.prefill_attention(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def expert_matmul_roofline(records):
    """The grouped products' least time over the pairs that fell on held
    experts: by the rounds' own counts where the program gives them
    (decode), by the held share of the router's outputs for a prefill
    dispatch's tokens."""
    secs = glm.kernel_seconds(records, EXPERT_KERNEL)
    hit = experts_hit(records) if secs else None
    if hit is None:
        return None
    cfg, K, n_moe, _f = _geometry(records)
    k = cfg["num_experts_per_tok"]
    held_share = cfg["n_routed_experts"] / float(cfg["expert_shard"]["of"])
    needed = 0.0
    for live, _rows, _sel in glm.decode_dispatches(records):
        needed += K * costs.least_seconds(
            *costs.expert_matmuls(cfg, live * k * held_share, hit),
            records["peaks"])
    for _bucket, lengths in glm.prefill_dispatches(records):
        needed += costs.least_seconds(
            *costs.expert_matmuls(cfg, sum(lengths) * k * held_share,
                                  cfg["n_routed_experts"]),
            records["peaks"])
    return glm.share(n_moe * needed, secs)


def _gather_ops(cfg):
    """``where(dtype, dims)`` of the decode's gather of the chosen rows
    and its page arithmetic, by their first result's shape: ``[slots,
    index_topk, ..]`` or, as the compiler lays them out, flat over a
    dispatch's chosen positions (the rows: ``[slots x index_topk, pool
    width]``)."""
    S, topk = cfg["pool"]["num_slots"], cfg["index_topk"]

    def where(_dtype, dims):
        dims = tuple(dims)
        return dims[:2] == (S, topk) or dims[:1] == (S * topk,)

    return where


def _selection_ops(cfg):
    """``where(dtype, dims)`` of the composed device paths of the
    selection, by their first result's shape: the decode's exact top-k (a
    sort of ``[slots, positions a slot]`` scores), its gather of the
    chosen rows (``_gather_ops``) and the prefill's scores and bisection
    over a block of queries (``[.., query block, bucket]`` for a bucket
    longer than ``index_topk``)."""
    pool = cfg["pool"]
    S, ps = pool["num_slots"], pool["page_size"]
    L = -(-(pool["max_prompt"] + pool["max_new_tokens"]) // ps) * ps
    masked = {t for t in pool["prefill_buckets"] if t > cfg["index_topk"]}
    gather = _gather_ops(cfg)

    def where(dtype, dims):
        dims = tuple(dims)
        return (dims == (S, L) or gather(dtype, dims)
                or (len(dims) >= 2 and dims[-2] == _QUERY_BLOCK
                    and dims[-1] in masked))

    return where


def _shaped_seconds(tr, where):
    """Seconds of the device operations, this model's three kernels left
    out, whose first result's shape ``where`` takes."""
    secs = 0.0
    for text, seconds, _calls in tr["ops"]:
        shape = trace_reduce.first_shape(text)
        if shape and where(*shape) and not any(
                k in trace_reduce.op_name(text) for k in _KERNELS):
            secs += seconds
    return secs


def sparse_attention_time_share(records):
    """Indexer + selection + gather + attention, decode and prefill, of
    the device's busy time: the three kernels by name and the composed
    paths by shape (``_selection_ops``). The indexer's projections and
    norms are fusions like any other layer's and are not in it."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0] for k in _KERNELS)
    if not secs:
        return None
    secs += _shaped_seconds(tr, _selection_ops(records["config"]))
    return 100.0 * secs / tr["busy_s"]


def expert_time_share(records):
    """The routed experts' share of the device's busy time: the grouped
    products with their metadata kernel and the sorts of routing and
    dispatch (``metric_lib_glm.expert_time_share``), less the sorts that
    are the selection's."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    where = _selection_ops(records["config"])
    secs = trace_reduce.kernel_time(tr, EXPERT_KERNEL)[0]
    if not secs:
        return None
    secs += trace_reduce.kernel_time(
        tr, "sort", where=lambda dtype, dims: not where(dtype, dims))[0]
    return 100.0 * secs / tr["busy_s"]


def _share_of_rounds(records, part, whole):
    def stat(rounds):
        total = sum(_head_counter(rounds, whole))
        if not total:
            return None
        return 100.0 * sum(_head_counter(rounds, part)) / total

    return glm.read_rounds(records, stat)


def selected_rows_share(records):
    """The rows a layer's decode attention reads over the rows its slots
    hold: the rounds' ``latent_rows_selected`` over their
    ``latent_rows_resident``."""
    return _share_of_rounds(records, "latent_rows_selected",
                            "latent_rows_resident")


def held_expert_token_share(records):
    """The (token, expert) choices of the decode steps that fell on an
    expert held here over all of them: the rounds'
    ``experts_held_tokens`` over their ``experts_routed_tokens``."""
    return _share_of_rounds(records, "experts_held_tokens",
                            "experts_routed_tokens")


def prefill_pad_share(records):
    from perfbench import metric_lib_jamba

    return glm.read_rounds(records, metric_lib_jamba.prefill_pad_share)
