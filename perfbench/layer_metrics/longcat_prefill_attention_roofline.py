"""Kernels: the flash forward at queries and keys of 192 beside values of 128
(two calls a layer a prefill dispatch): the lower triangle's two products
at their own widths, no padded lane counted, against the kernel's own
device time."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.prefill_attention_roofline(records)
