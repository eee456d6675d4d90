"""Serving host plane: the decode steps' (token, expert) choices that fell on
an expert this chip holds over all of them: the rounds'
``experts_held_tokens`` over their ``experts_routed_tokens`` (16 of 256
experts held: 6.25% under an even spread)."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.held_expert_token_share(records)
