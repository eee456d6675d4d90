"""Set-up: seconds JAX spent tracing the Python program to jaxprs and
lowering them to MLIR modules before the window opened
(``exec_cache.stats()`` ``trace_seconds + lower_seconds``): what every
process start pays again, compile cache or not."""

from perfbench import program_records as pr


def read(records):
    return pr.read_trace_lower_s(records)
