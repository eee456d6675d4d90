"""Model step: device time of one decode dispatch of the hybrid state-space
decoder (``tokens_per_dispatch`` tokens for every slot): the median length
of the compiled-program runs in the trace that hold the one-token state
update kernel (``ssm_state_update``)."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.decode_dispatch_ms(records)
