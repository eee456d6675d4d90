"""Model step: model FLOP/s utilisation of the traced steps.

Operations one optimizer step's forward and backward passes need
(``kernel_costs.transformer_train_step``: 6 x the non-embedding parameters
each token passes, the output projection, attention; nothing recomputed)
over the step's DEVICE time and chips x the bf16 peak. Host gaps between
steps are not in it: they show in ``train_device_idle_share`` and in
``train_tokens_per_s``."""

from perfbench import kernel_costs
from perfbench import metric_lib as lib


def read(records):
    step_ms = lib.train_step_ms(records)
    if not step_ms:
        return None
    ops = kernel_costs.transformer_train_step(records["config"],
                                              records["train"]["batch"])
    peak = records["chips"] * records["peaks"]["bf16_flops_per_s"]
    return 100.0 * ops / (step_ms / 1e3) / peak
