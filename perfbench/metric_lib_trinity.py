"""What the ``trinity_`` per-layer metric readers share. The cell's records
go by the keys the other serving cells give (``records["serve"]``,
``entries/windowed_frontend.py``), so the host plane is read by the
``glm_`` readers that were there (``BENCHMARK.json`` lists this cell under
them) and ``metric_lib_glm``'s helpers for the traced dispatches are used
as they are; here is what finds THIS model's kernels by the names a device
event carries and counts their least time (``kernel_costs_trinity.py``).
A decode dispatch's record is ``(live slots, resident rows, rows a window
layer's queries could see)`` at its first step. A reader returns None
when there is nothing to read: no device trace (the CPU rehearsals), or a
program without the kernel, span or counter."""

from perfbench import kernel_costs_trinity as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import trace_reduce

# kernels/window_paged_attention.py, gqa_paged_attention.py,
# flash_attention.py, grouped_matmul.py: the names of their pallas_calls,
# which a device event carries
WINDOW_KERNEL = "gqa_window_decode_attention"
FULL_KERNEL = "gqa_paged_decode_attention"
PREFILL_KERNEL = glm.PREFILL_KERNEL
EXPERT_KERNEL = glm.EXPERT_KERNEL
ATTENTION_KERNELS = (WINDOW_KERNEL, FULL_KERNEL, PREFILL_KERNEL)


def _geometry(records):
    cfg = records["config"]
    kinds = list(cfg["layer_types"])
    n_window = kinds.count(costs.SLIDING)
    return (cfg, cfg["pool"]["tokens_per_dispatch"], n_window,
            len(kinds) - n_window,
            cfg["num_hidden_layers"] - cfg.get("num_dense_layers", 0))


def decode_dispatch_ms(records):
    return glm.module_ms(records, WINDOW_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, PREFILL_KERNEL)


def decode_hbm_roofline(records):
    """Least seconds to read what the traced decode dispatches had to
    read (weights once a token step, the live rows of the full layers and
    the visible rows of the window layers once), over the device time of
    the runs that hold the window decode kernel. Later steps of a
    dispatch are counted with the first step's visible rows (a row more a
    slot a step in a full layer is added; in a window layer only a slot
    still under its window gains one)."""
    runs = lib.module_runs(records, holding=WINDOW_KERNEL)
    calls = glm.decode_dispatches(records)
    if not runs or not calls:
        return None
    cfg, K, _w, _f, _m = _geometry(records)
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, rows + j * live, seen)
                    for j in range(K)) / bw for live, rows, seen in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def _decode_attention_roofline(records, kernel, layers, rows_of):
    secs = glm.kernel_seconds(records, kernel)
    if not secs:
        return None
    cfg, K, _w, _f, _m = _geometry(records)
    needed = layers * sum(
        costs.least_seconds(*costs.decode_attention(
            cfg, rows_of(call, j), call[0]), records["peaks"])
        for call in glm.decode_dispatches(records) for j in range(K))
    return glm.share(needed, secs)


def window_decode_attention_roofline(records):
    """The visible rows (never the whole pages the ring holds) of the
    window layers' calls over the window kernel's own time."""
    n_window = _geometry(records)[2]
    return _decode_attention_roofline(
        records, WINDOW_KERNEL, n_window, lambda call, j: call[2])


def full_decode_attention_roofline(records):
    n_full = _geometry(records)[3]
    return _decode_attention_roofline(
        records, FULL_KERNEL, n_full,
        lambda call, j: call[1] + j * call[0])


def prefill_attention_roofline(records):
    secs = glm.kernel_seconds(records, PREFILL_KERNEL)
    if not secs:
        return None
    needed = sum(
        costs.least_seconds(*costs.prefill_attention(records["config"],
                                                     lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def expert_matmul_roofline(records):
    secs = glm.kernel_seconds(records, EXPERT_KERNEL)
    if not secs:
        return None
    cfg, K, _w, _f, n_moe = _geometry(records)
    k = cfg["num_experts_per_tok"]
    pairs = [call[0] * k for call in glm.decode_dispatches(records)
             for _j in range(K)]
    pairs += [sum(lengths) * k
              for _bucket, lengths in glm.prefill_dispatches(records)]
    needed = n_moe * sum(costs.least_seconds(
        *costs.expert_matmuls(cfg, p), records["peaks"]) for p in pairs)
    return glm.share(needed, secs)


# the grouped products with their metadata kernel and the sorts
expert_time_share = glm.expert_time_share


def attention_time_share(records):
    """The three attention kernels' share of the device's busy time: the
    window and the full decode kernels and the prefill's flash kernel.
    The projections, norms, RoPE and the gate are fusions like any other
    layer's and are not in it."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0] for k in ATTENTION_KERNELS)
    return 100.0 * secs / tr["busy_s"] if secs else None


def window_rows_share(rounds):
    """100 x the rounds' ``window_rows_visible`` over their
    ``full_rows_visible``: the rows a window layer's decode reads over
    what a full cache in its place would have read. None where the
    program does not count them."""
    heads = [r["spans"][0] for r in rounds]
    full = sum(h.get("full_rows_visible", 0) for h in heads)
    if not full:
        return None
    return 100.0 * sum(h.get("window_rows_visible", 0)
                       for h in heads) / full


def read_window_rows_share(records):
    from perfbench import harness

    def stat(rounds):
        heads = [r["spans"][0] for r in rounds
                 if "window_pages_in_use" in r["spans"][0]]
        if heads:
            harness.log(
                "pages in use at a decode dispatch, the median round: %d "
                "full, %d window; %d window pages given back over %d "
                "rounds"
                % (lib.median([h["full_pages_in_use"] for h in heads]),
                   lib.median([h["window_pages_in_use"] for h in heads]),
                   sum(h["window_pages_released"] for h in heads),
                   len(heads)))
        return window_rows_share(rounds)

    return glm.read_rounds(records, stat)


def read_prefill_pad_share(records):
    from perfbench import metric_lib_jamba

    return glm.read_rounds(records, metric_lib_jamba.prefill_pad_share)
