"""Kernels: the indexer's decode scores (``index_score_decode``: every head's
product with every resident narrow key, each key read once) against the
kernel's own device time."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.index_score_decode_roofline(records)
