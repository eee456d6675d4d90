"""Serving host plane: the share of a prefill dispatch's token places that
are padding: the rounds' ``prefill_pad_tokens`` over those plus
``prefill_tokens`` (six buckets, a power of two apart)."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.read_prefill_pad_share(records)
