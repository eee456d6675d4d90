"""Model step: the (token, expert) choices of the decode steps that fell on
one of the 40 experts held here, of all 8 a token: the rounds'
``experts_held_tokens`` over their ``experts_routed_tokens`` (12.5% where
the router spreads evenly over its 320 outputs)."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.held_expert_token_share(records)
