"""Kernels: the flash forward kernel's share of its roofline over the traced
prefill dispatches: the products of the visible band of each prompt's OWN
length (a window layer's band is 2048 wide; bucket padding is not work;
``kernel_costs_trinity.prefill_attention``) over the kernel's time."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.prefill_attention_roofline(records)
