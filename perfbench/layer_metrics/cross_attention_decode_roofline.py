"""Kernels: the cross attention of the decode step (the flash forward
kernel at query length 1) as a share of its roofline over the traced
dispatches. Needed: each live slot's real source keys and values read
once, not the 256 padded positions; memory bound."""

from perfbench import metric_lib as lib


def read(records):
    return lib.attention_roofline(
        records, lib.FLASH_FWD, 'cross',
        where=lambda dtype, dims: len(dims) == 4 and dims[2] == 1)
