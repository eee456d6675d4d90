"""Model step: the decode steps' choices that fell on a real expert this
chip holds over all of them: the rounds' ``experts_held_tokens`` over
their ``experts_routed_tokens`` (16 of 768 outputs: 2.1% under an even
spread)."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.held_expert_token_share(records)
