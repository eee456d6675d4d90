"""Kernels: the flash forward kernel's share of its roofline over the
traced steps: the operations and bytes its attentions need
(``kernel_costs.flash_attention_fwd``, bf16 operands) against the kernel's
time in the trace. A third of the calls are causal."""

from perfbench import kernel_costs, trace_reduce
from perfbench import metric_lib as lib


def read(records):
    tr = records.get("trace")
    if not tr:
        return None
    secs, calls = trace_reduce.kernel_time(tr, lib.FLASH_FWD)
    if not calls:
        return None
    cfg = records["config"]
    T = cfg["max_length"]
    per_chip = records["train"]["batch"] // records["chips"]
    dh = cfg["d_model"] // cfg["n_head"]
    full = kernel_costs.flash_attention_fwd(per_chip, cfg["n_head"], T, T,
                                            dh, 2)
    causal = kernel_costs.flash_attention_fwd(per_chip, cfg["n_head"], T, T,
                                              dh, 2, causal=True)
    # per layer: encoder self and cross are full, decoder self is causal
    ops = (2 * full[0] + causal[0]) * calls / 3.0
    moved = (2 * full[1] + causal[1]) * calls / 3.0
    return kernel_costs.roofline_share(ops, moved, secs,
                                       records["peaks"])[0]
