"""Model step: the per-slot arrays' bytes (matrix state and window, read and
written) of all the bytes a decode token step must move, in the median
round, from the program's counters ``state_bytes_live``,
``state_slots_live``, ``kv_rows_visible`` and ``experts_held_hit``."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.state_bytes_share(records)
