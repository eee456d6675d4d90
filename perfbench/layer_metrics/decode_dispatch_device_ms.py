"""Model step: device time of one decode dispatch (4 tokens for every
slot), the median length of the compiled-program runs in the trace that
hold the paged decode kernel."""

from perfbench import metric_lib as lib


def read(records):
    return lib.decode_dispatch_ms(records)
