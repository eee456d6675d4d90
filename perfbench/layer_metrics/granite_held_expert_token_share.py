"""Model step: the (token, expert) choices of the decode steps that fell on
one of the 36 experts held here, of all 10 a token: the rounds'
``experts_held_tokens`` over their ``experts_routed_tokens`` (50% where the
router spreads evenly over its 72 outputs)."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.held_expert_token_share(records)
