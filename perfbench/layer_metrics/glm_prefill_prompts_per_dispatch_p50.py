"""Serving host plane: prompts a prefill dispatch, the median over the
window's rounds that prefilled (the round's ``prefill_prompts`` over its
``prefill_dispatches``)."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.read_rounds(records, lib.prefill_prompts_per_dispatch_p50)
