"""Model step: device time of one optimizer step, the median length of
the compiled-program runs that hold the flash backward kernel (chip 0)."""

from perfbench import metric_lib as lib


def read(records):
    return lib.train_step_ms(records)
