"""Serving host plane: the share of a prefill dispatch's token places that
are padding: the rounds' ``prefill_pad_tokens`` over those plus
``prefill_tokens`` (the products walk them; the scan does not). The
median round's ``state_slots_live`` is printed on an earlier line."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.read_prefill_pad_share(records)
