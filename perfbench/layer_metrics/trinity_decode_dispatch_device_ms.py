"""Model step: device time of one decode dispatch (4 tokens a slot): the
median length of the compiled-program runs in the trace that hold the
window decode kernel (``gqa_window_decode_attention``)."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.decode_dispatch_ms(records)
