"""Serving host plane: the share of a prefill dispatch's token places that
are padding: the rounds' ``prefill_pad_tokens`` over those plus
``prefill_tokens`` (five buckets, a power of two apart, one prompt a
dispatch)."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.prefill_pad_share(records)
