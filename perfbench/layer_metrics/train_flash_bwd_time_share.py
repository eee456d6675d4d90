"""Kernels: the two flash backward kernels' share of the device's busy
time over the traced steps."""

from perfbench import trace_reduce
from perfbench import metric_lib as lib


def read(records):
    tr = records.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    secs = sum(trace_reduce.kernel_time(tr, k)[0] for k in lib.FLASH_BWD)
    return 100.0 * secs / tr["busy_s"] if secs else None
