"""Model step: the share of a prefill dispatch's token places that are
padding: the rounds' ``prefill_pad_tokens`` over those plus
``prefill_tokens`` (four buckets a power of two apart, a program a rung
of prompt rows)."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.prefill_pad_share(records)
