"""Ops of a gated delta-rule linear-attention mixer (Kimi Delta Attention,
a decay a key channel; Gated DeltaNet, a decay a head) for SERVING: the
decay and step gates, the chunked prefill and the one-token update over
per-slot MATRIX state (``kernels/delta_rule.py``), and the gated per-head
norm of the mixer's output.

Like ``ssm_ops.py`` every op works on flat token rows ``[N, ...]`` (a
decode step's ``N`` is the slot count, a prefill dispatch's is ``prompts x
bucket length``, one prompt a bucket row) and none has a gradient. The
state ``[slots, heads / pack, dk, pack * dv]`` (``delta_rule.pack_heads``)
is float32 whatever the parameters' dtype, as are the log decay, ``beta`` and the mixer's output before its
norm. The short convolution before them is ``ssm_ops``'s
(``ssm_causal_conv`` / ``ssm_conv_step``) over the ``q | k | v`` row.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op

_F32 = jnp.float32


def _flat(x):
    return jnp.reshape(x, (-1,))


def _lower_delta_rule_gates(ctx, ins, attrs):
    """The log decay ``g = -exp(A_log_h) * softplus(f + dt_bias)``, a key
    channel (``f`` ``[N, heads * dk]``, the low-rank gate's output) or a
    head (``f`` ``[N, heads]``), float32, and the step ``beta = beta_scale
    * sigmoid(b)``."""
    f = ins["X"][0].astype(_F32)
    H = int(attrs["heads"])
    rate = jnp.exp(ins["ALog"][0].astype(_F32))             # [H]
    soft = jax.nn.softplus(f + ins["DtBias"][0].astype(_F32))
    g = -(soft.reshape(f.shape[0], H, -1) * rate[None, :, None])
    beta = float(attrs["beta_scale"]) * jax.nn.sigmoid(
        ins["B"][0].astype(_F32))
    return {"G": g.reshape(f.shape), "Beta": beta}


register_op(
    "delta_rule_gates", inputs=["X", "DtBias", "ALog", "B"],
    outputs=["G", "Beta"], attrs={"heads": 1, "beta_scale": 1.0},
    lower=_lower_delta_rule_gates, grad=None)


def _lower_delta_rule_prefill(ctx, ins, attrs):
    """The delta rule over a prefill dispatch's prompts: the mixer's
    output for every token and each prompt's state after its last real
    token, ``state_pack`` heads a tile of it (the served array's)."""
    from paddle_tpu.kernels.delta_rule import chunk_prefill, pack_heads

    lens = _flat(ins["Lens"][0]).astype(jnp.int32)
    B = lens.shape[0]

    def prompts(x):
        return x.reshape(B, -1, x.shape[-1])

    o, state = chunk_prefill(
        prompts(ins["Q"][0]), prompts(ins["K"][0]), prompts(ins["V"][0]),
        prompts(ins["G"][0]), prompts(ins["Beta"][0]), lens)
    return {"Out": o.reshape(-1, o.shape[-1]),
            "State": pack_heads(state, int(attrs.get("state_pack", 1)))}


register_op(
    "delta_rule_prefill", inputs=["Q", "K", "V", "G", "Beta", "Lens"],
    outputs=["Out", "State"], attrs={"state_pack": 1},
    lower=_lower_delta_rule_prefill, grad=None)


def _lower_delta_rule_state_update(ctx, ins, attrs):
    from paddle_tpu.kernels.delta_rule import state_update

    o, state = state_update(
        ins["State"][0], ins["Q"][0], ins["K"][0], ins["V"][0],
        ins["G"][0], ins["Beta"][0], _flat(ins["Live"][0]))
    return {"Out": o, "StateOut": state}


register_op(
    "delta_rule_state_update",
    inputs=["State", "Q", "K", "V", "G", "Beta", "Live"],
    outputs=["Out", "StateOut"], lower=_lower_delta_rule_state_update,
    grad=None)


def _lower_gated_head_norm(ctx, ins, attrs):
    """``RMSNorm_head(x) * sigmoid(gate)`` (``gate_act`` "silu":
    ``silu(gate)``): the norm over each head's values with one scale
    vector, in float32, rounded once to the gate's dtype."""
    x, gate = ins["X"][0].astype(_F32), ins["Gate"][0]
    H = int(attrs["heads"])
    xh = x.reshape(x.shape[0], H, -1)
    var = jnp.mean(jnp.square(xh), axis=-1, keepdims=True)
    y = xh * jax.lax.rsqrt(var + float(attrs["epsilon"])) \
        * ins["Scale"][0].astype(_F32)
    act = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}[
        attrs.get("gate_act", "sigmoid")]
    return {"Out": (y.reshape(x.shape)
                    * act(gate.astype(_F32))).astype(gate.dtype)}


register_op(
    "gated_head_norm", inputs=["X", "Scale", "Gate"], outputs=["Out"],
    attrs={"heads": 1, "epsilon": 1e-5, "gate_act": "sigmoid"},
    lower=_lower_gated_head_norm,
    grad=None)
