"""Ops of a Mamba-2 mixer (state-space duality) for SERVING: the chunked
prefill and the one-token update over per-slot MATRIX state
(``kernels/ssd.py``), the gated whole-row norm of the mixer's output, and
the residual add of a model whose branches are scaled.

Like ``ssm_ops.py`` every op works on flat token rows ``[N, ...]`` (a
decode step's ``N`` is the slot count, a prefill dispatch's is ``prompts x
bucket length``, one prompt a bucket row) and none has a gradient. The
state ``[slots, lane groups, d_state, group lanes]`` (``kernels/ssd.py``
has the layout) is float32 whatever the parameters' dtype, as are
``Delta``, ``A`` and the mixer's output before its norm. The short convolution before them is ``ssm_ops``'s
(``ssm_causal_conv`` / ``ssm_conv_step``) over the ``x | B | C`` row.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op

_F32 = jnp.float32


def _flat(x):
    return jnp.reshape(x, (-1,))


def _operands(ins, attrs):
    """(x, Delta, A, B, C, D) of a convolved ``x | B | C`` row and the raw
    step: ``Delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head,
    both float32."""
    xbc = ins["XBC"][0]
    n = int(attrs["d_state"])
    w = xbc.shape[-1] - 2 * n
    delta = jax.nn.softplus(ins["Dt"][0].astype(_F32)
                            + ins["DtBias"][0].astype(_F32))
    return (xbc[..., :w], delta, -jnp.exp(ins["ALog"][0].astype(_F32)),
            xbc[..., w:w + n], xbc[..., w + n:], ins["DSkip"][0])


def _lower_ssd_prefill(ctx, ins, attrs):
    """The recurrence over a prefill dispatch's prompts: the mixer's
    output for every token (float32, 0 on padding) and each prompt's state
    after its last real token."""
    from paddle_tpu.kernels.ssd import chunk_prefill

    lens = _flat(ins["Lens"][0]).astype(jnp.int32)
    B = lens.shape[0]
    x, delta, a, b, c, d_skip = _operands(ins, attrs)

    def prompts(v):
        return v.reshape(B, -1, v.shape[-1])

    y, state = chunk_prefill(prompts(x), prompts(delta), a, prompts(b),
                             prompts(c), d_skip, lens)
    return {"Out": y.reshape(-1, y.shape[-1]), "State": state}


register_op(
    "ssd_prefill", inputs=["XBC", "Dt", "DtBias", "ALog", "DSkip", "Lens"],
    outputs=["Out", "State"], attrs={"d_state": 1},
    lower=_lower_ssd_prefill, grad=None)


def _lower_ssd_state_update(ctx, ins, attrs):
    from paddle_tpu.kernels.ssd import state_update

    x, delta, a, b, c, d_skip = _operands(ins, attrs)
    y, state = state_update(ins["State"][0], x, delta, a, b, c, d_skip,
                            _flat(ins["Live"][0]))
    return {"Out": y, "StateOut": state}


register_op(
    "ssd_state_update",
    inputs=["State", "XBC", "Dt", "DtBias", "ALog", "DSkip", "Live"],
    outputs=["Out", "StateOut"], attrs={"d_state": 1},
    lower=_lower_ssd_state_update, grad=None)


def _lower_gated_row_norm(ctx, ins, attrs):
    """``RMSNorm(x * silu(gate)) * scale`` over the WHOLE row (one group):
    the gate is multiplied in BEFORE the statistics; float32, rounded once
    to the gate's dtype."""
    gate = ins["Gate"][0]
    g32 = gate.astype(_F32)
    v = ins["X"][0].astype(_F32) * g32 * jax.nn.sigmoid(g32)
    var = jnp.mean(jnp.square(v), axis=-1, keepdims=True)
    return {"Out": (v * jax.lax.rsqrt(var + float(attrs["epsilon"]))
                    * ins["Scale"][0].astype(_F32)).astype(gate.dtype)}


register_op(
    "gated_row_norm", inputs=["X", "Gate", "Scale"], outputs=["Out"],
    attrs={"epsilon": 1e-5}, lower=_lower_gated_row_norm, grad=None)


def _lower_scaled_residual(ctx, ins, attrs):
    """``x + scale * y`` in float32, rounded once to ``x``'s dtype."""
    x = ins["X"][0]
    return {"Out": (x.astype(_F32) + float(attrs["scale"])
                    * ins["Y"][0].astype(_F32)).astype(x.dtype)}


register_op(
    "scaled_residual", inputs=["X", "Y"], outputs=["Out"],
    attrs={"scale": 1.0}, lower=_lower_scaled_residual, grad=None)
