"""Weights of the hybrid Mamba-2 / grouped-query decoder with routed
experts (``granitemoehybrid``) from the seed, made on the device in ONE
jitted call, in the type they are served in (bfloat16; ``dt_bias``,
``a_log`` and ``d_skip`` float32), as ``weights_solar.py`` makes its
family's.

``make`` returns ``{program name: array}``
(``paddle_tpu.models.ssd_moe_decoder.parameter_shapes`` names them: the
HELD experts only, the router's every output, the vocabulary's slice);
``tree`` lays the SAME arrays out as the reference's nested tree
(``reference/ssd_moe_decoder.py``), so both sides hold one copy.

Initialisers (seeded weights stand for a checkpoint; the configuration's
``assumed`` lists them): a matrix is uniform with variance 1 / fan-in, the
embedding has variance 1 (before ``embedding_multiplier``), a norm's scale
is 1 +- 0.1, the convolution's weight has variance 1 / taps and its bias is
uniform in +-0.1; ``a_log = log(U(1, 16))`` a head, ``dt_bias`` the inverse
softplus of a log-uniform 1e-3..1e-1 and ``D`` = 1 (Mamba-2's own
initialisers): a state that neither dies in one token nor never forgets.
"""

import jax
import jax.numpy as jnp

_MAMBA = ("in_z", "in_xbc", "in_dt", "conv_w", "conv_b", "dt_bias", "a_log",
          "d_skip", "mix_norm", "out_proj")
_ATTENTION = ("q", "k", "v", "o")
_MOE = {"router": "router", "gate": "experts_gate", "up": "experts_up",
        "down": "experts_down", "shared_gate": "shared_gate",
        "shared_up": "shared_up", "shared_down": "shared_down"}


def _leaf(key, name, shape, dtype):
    dtype = jnp.dtype(dtype)

    def uniform(lo, hi, dt=dtype):
        return jax.random.uniform(key, shape, dt, lo, hi)

    if name.endswith("a_log"):
        return jnp.log(uniform(1.0, 16.0))
    if name.endswith("dt_bias"):
        delta = jnp.exp(uniform(jnp.log(1e-3), jnp.log(1e-1), jnp.float32))
        return (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype)
    if name.endswith("d_skip"):
        return jnp.ones(shape, dtype)
    if name.endswith("conv_b"):
        return uniform(-0.1, 0.1)
    if name.endswith("norm"):
        return uniform(0.9, 1.1)
    var = (1.0 if name == "smd_embed" else
           1.0 / shape[0] if name.endswith("conv_w") else 1.0 / shape[-2])
    a = (3.0 * var) ** 0.5
    return uniform(-a, a)


def make(cfg, seed, dtype="bfloat16"):
    """{program name: array} on the default device, from ``seed`` alone."""
    from paddle_tpu.models.ssd_moe_decoder import parameter_shapes

    shapes = parameter_shapes(cfg, dtype)
    names = list(shapes)

    def build(key):
        return {name: _leaf(jax.random.fold_in(key, n), name,
                            *shapes[name])
                for n, name in enumerate(names)}

    # seeds run past 2**31: fold the high bits in instead of truncating
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def tree(named, cfg):
    """The reference's nested tree over the same arrays."""
    layers = []
    for i, kind in enumerate(cfg["layer_types"]):
        def get(part, i=i):
            return named["smd_%d_%s" % (i, part)]

        layers.append({
            "in_norm": get("in_norm"), "ff_norm": get("ff_norm"),
            "mixer": {k: get(k) for k in (
                _ATTENTION if kind == "attention" else _MAMBA)},
            "ffn": {k: get(v) for k, v in _MOE.items()}})
    return {"embed": named["smd_embed"],
            "final_norm": named["smd_final_norm"], "layers": layers}
