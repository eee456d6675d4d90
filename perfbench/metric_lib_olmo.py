"""The per-layer readers of the ``olmo_hybrid_8l`` cell, as FUNCTIONS: no
entry of ``BENCHMARK.json`` names them yet (``per_layer`` holds its most,
128 entries, and a PR may only add: the ``benchmark`` PR that makes room
appends an entry ``olmo_<name>`` and a one-line ``layer_metrics/
olmo_<name>.py`` each, ``READERS`` has the names; ``tests/perfbench`` holds
them to hand counts meanwhile). The cell's records go by the keys the other
decoder-only cells give (``records["serve"]``, ``entries/
gated_delta_decoder_frontend.py``), so the host plane is read by the
``glm_`` readers that were there (``BENCHMARK.json`` lists this cell under
them), ``metric_lib_glm``'s helpers for the traced dispatches are used as
they are, and the readers that read no key of a configuration (the linear
mechanism's time share, the prefill's padding) are ``metric_lib_solar``'s
themselves. No kernel is new: here is what finds THIS model's kernels by
the names a device event carries (the delta rule's and the convolution's,
the grouped-query decode at a group of one, the flash forward) and counts
their least time at this configuration's keys (``kernel_costs_olmo.py``:
the delta rule at keys of 96 beside values of 192 with a decay a head, the
state at its PUBLISHED size). A decode dispatch's record is ``(live slots,
resident rows)`` at its first step. A reader returns None when there is
nothing to read: no device trace (the CPU rehearsals), or a program
without the kernel, span or counter."""

from perfbench import kernel_costs_olmo as costs
from perfbench import metric_lib as lib
from perfbench import metric_lib_glm as glm
from perfbench import metric_lib_solar as solar
from perfbench import trace_reduce

# kernels/delta_rule.py, gqa_paged_attention.py, flash_attention.py: the
# names of their pallas_calls
UPDATE_KERNEL = solar.UPDATE_KERNEL
CHUNK_KERNEL = solar.CHUNK_KERNEL
DECODE_KERNEL = solar.GQA_KERNEL
PREFILL_KERNEL = solar.PREFILL_KERNEL


def _geometry(records):
    """(configuration, token steps a dispatch, full layers, linear
    layers)."""
    cfg = records["config"]
    full = sum(k == "full_attention" for k in cfg["layer_types"])
    return (cfg, cfg["pool"]["tokens_per_dispatch"], full,
            len(cfg["layer_types"]) - full)


def decode_dispatch_ms(records):
    return glm.module_ms(records, UPDATE_KERNEL)


def prefill_dispatch_ms(records):
    return glm.module_ms(records, CHUNK_KERNEL)


linear_time_share = solar.linear_time_share
read_prefill_pad_share = solar.read_prefill_pad_share


def decode_hbm_roofline(records):
    """Least seconds to move what the traced decode dispatches had to
    move (``kernel_costs_olmo.decode_step_bytes`` a token step: every
    weight but the embedding, the live slots' state twice, the resident
    K/V rows), over the device time of the runs that hold the state
    update kernel."""
    runs = lib.module_runs(records, holding=UPDATE_KERNEL)
    calls = glm.decode_dispatches(records)
    if not runs or not calls:
        return None
    cfg, K = _geometry(records)[:2]
    bw = records["peaks"]["hbm_bytes_per_s"]
    per_call = [sum(costs.decode_step_bytes(cfg, live, rows + j * live)
                    for j in range(K)) / bw for live, rows in calls]
    return glm.share(sum(per_call) / len(per_call),
                     sum(m["seconds"] for m in runs) / len(runs))


def state_update_roofline(records):
    """``delta_rule_state_update``: one call a linear layer a token."""
    secs = glm.kernel_seconds(records, UPDATE_KERNEL)
    if not secs:
        return None
    cfg, K, _full, n_linear = _geometry(records)
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
    cfg, _K, _full, n_linear = _geometry(records)
    needed = n_linear * sum(
        costs.least_seconds(*costs.chunk_prefill(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def mha_decode_attention_roofline(records):
    """``gqa_paged_decode_attention`` at 30 heads on 30: one call a full
    layer a token step."""
    secs = glm.kernel_seconds(records, DECODE_KERNEL)
    if not secs:
        return None
    cfg, K, n_full, _lin = _geometry(records)
    needed = n_full * sum(
        costs.least_seconds(*costs.mha_decode_attention(
            cfg, rows + j * live, live), records["peaks"])
        for live, rows in glm.decode_dispatches(records) for j in range(K))
    return glm.share(needed, secs)


def prefill_attention_roofline(records):
    """``flash_attention_fwd`` at 30 heads of 128: one call a full layer a
    prefill dispatch."""
    secs = glm.kernel_seconds(records, PREFILL_KERNEL)
    if not secs:
        return None
    cfg, _K, n_full, _lin = _geometry(records)
    needed = n_full * sum(
        costs.least_seconds(*costs.prefill_attention(cfg, lengths),
                            records["peaks"])
        for _bucket, lengths in glm.prefill_dispatches(records))
    return glm.share(needed, secs)


def attention_time_share(records):
    """The full layers' two kernels' share of the device's busy time: the
    paged decode and the flash forward. NOT in it, as in
    ``metric_lib_solar.linear_time_share``: the layers' products and the q
    and k norms, which are fusions like any other layer's."""
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0]
               for k in (DECODE_KERNEL, PREFILL_KERNEL))
    return 100.0 * secs / tr["busy_s"] if secs else None


def _bytes_share(records, part):
    """``part`` of ``kernel_costs_olmo.decode_step_parts`` over their sum,
    in the median round, from the program's counters
    (``state_slots_live``, ``kv_rows_visible``) and what the program's
    geometry says its arrays hold (``state_bytes_slot_layer``: a padded
    layout of the state reads as a larger share; ``kv_row_bytes``)."""
    cfg = records["config"]
    geo = (glm.mine(records) or {}).get("geometry", {})
    state, row = geo.get("state_bytes_slot_layer"), geo.get("kv_row_bytes")

    def stat(rounds):
        shares = []
        for r in rounds:
            head = r["spans"][0]
            if not head.get("state_slots_live"):
                continue
            parts = costs.decode_step_parts(
                cfg, head["state_slots_live"], head["kv_rows_visible"],
                state, row)
            shares.append(100.0 * parts[part] / sum(parts.values()))
        return lib.median(shares)

    return glm.read_rounds(records, stat) if state and row else None


def state_bytes_share(records):
    return _bytes_share(records, "state")


def kv_bytes_share(records):
    return _bytes_share(records, "rows")


# the entries a ``benchmark`` PR that has made room declares, and what
# each reads with
READERS = {
    "olmo_decode_dispatch_device_ms": decode_dispatch_ms,
    "olmo_prefill_dispatch_device_ms": prefill_dispatch_ms,
    "olmo_decode_hbm_roofline": decode_hbm_roofline,
    "olmo_state_update_roofline": state_update_roofline,
    "olmo_chunk_prefill_roofline": chunk_prefill_roofline,
    "olmo_mha_decode_attention_roofline": mha_decode_attention_roofline,
    "olmo_prefill_attention_roofline": prefill_attention_roofline,
    "olmo_linear_time_share": linear_time_share,
    "olmo_attention_time_share": attention_time_share,
    "olmo_state_bytes_share": state_bytes_share,
    "olmo_kv_bytes_share": kv_bytes_share,
    "olmo_prefill_pad_share": read_prefill_pad_share,
}
