"""Weights of the dense Gated DeltaNet / multi-head attention decoder
(``olmo_hybrid``) from the seed, made on the device in ONE jitted call, in
the type they are served in (bfloat16; ``a_log`` and ``dt_bias`` float32),
as ``weights_solar.py`` makes its family's.

``make`` returns ``{program name: array}``
(``paddle_tpu.models.gated_delta_decoder.parameter_shapes`` names them);
``tree`` lays the SAME arrays out as the reference's nested tree
(``reference/gated_delta_decoder.py``), so both sides hold one copy.

Initialisers (seeded weights stand for a checkpoint; the configuration's
``assumed`` lists them): a matrix is uniform with variance 1 / fan-in, the
embedding has variance 1, a norm's scale is 1 +- 0.1, the convolution's
weight has variance 1 / taps; ``a_log = log(U(1, 16))`` a head and
``dt_bias`` the inverse softplus of a log-uniform 1e-3..1e-1 (the public
Gated DeltaNet initialisers): a state that neither dies in one token nor
never forgets.
"""

import jax
import jax.numpy as jnp

_LINEAR = ("qkv", "conv_w", "a", "dt_bias", "a_log", "beta", "gate",
           "o_norm", "o")
_FULL = ("q", "k", "v", "q_norm", "k_norm", "o")
_FFN = {"gate": "ffn_gate", "up": "ffn_up", "down": "ffn_down"}


def _leaf(key, name, shape, dtype):
    dtype = jnp.dtype(dtype)

    def uniform(lo, hi, dt=dtype):
        return jax.random.uniform(key, shape, dt, lo, hi)

    if name.endswith("a_log"):
        return jnp.log(uniform(1.0, 16.0))
    if name.endswith("dt_bias"):
        delta = jnp.exp(uniform(jnp.log(1e-3), jnp.log(1e-1), jnp.float32))
        return (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype)
    if name.endswith("norm"):
        return uniform(0.9, 1.1)
    var = (1.0 if name == "gdd_embed" else
           1.0 / shape[0] if name.endswith("conv_w") else 1.0 / shape[-2])
    a = (3.0 * var) ** 0.5
    return uniform(-a, a)


def make(cfg, seed, dtype="bfloat16"):
    """{program name: array} on the default device, from ``seed`` alone."""
    from paddle_tpu.models.gated_delta_decoder import parameter_shapes

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
            return named["gdd_%d_%s" % (i, part)]

        layers.append({
            "attn_norm": get("attn_norm"), "ff_norm": get("ff_norm"),
            "mixer": {k: get(k) for k in
                      (_FULL if kind == "full_attention" else _LINEAR)},
            "ffn": {k: get(v) for k, v in _FFN.items()}})
    return {"embed": named["gdd_embed"], "head": named["gdd_head"],
            "final_norm": named["gdd_final_norm"], "layers": layers}
