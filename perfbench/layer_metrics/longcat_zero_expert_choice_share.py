"""Model step: the decode steps' (token, router output) choices that fell on
a zero-compute (identity) expert over all of them: the rounds'
``experts_zero_tokens`` over their ``experts_routed_tokens`` (256 of 768
outputs: a third under an even spread)."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.zero_expert_choice_share(records)
