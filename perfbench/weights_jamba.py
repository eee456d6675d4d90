"""Weights of the hybrid state-space decoder from the seed, made on the
device in ONE jitted call, in the type they are served in (bfloat16).

``make`` returns ``{program name: array}``
(``paddle_tpu.models.hybrid_ssm_decoder.parameter_shapes`` names them);
``tree`` lays the SAME arrays out as the reference's nested tree
(``reference/hybrid_ssm_decoder.py``), so both sides hold one copy and the
reference takes nothing the program has made.

Initialisers (seeded weights stand for a checkpoint; the configuration's
``assumed`` lists them): a matrix is uniform with variance 1 / fan-in, the
embedding (tied: it is the output head too) has variance 1, a norm's scale
is 1 +- 0.1, the convolution's weight has variance 1 / d_conv and its bias
is uniform in +-0.1, ``D`` is 1; ``a_log[k] = log(k + 1)`` so that ``A``
spans -1..-d_state, and ``dt_bias`` is the inverse softplus of a
log-uniform 1e-3..1e-1, so that ``Delta`` starts in that range: a state
that neither dies in one token nor never forgets (Mamba's own
initialisers).
"""

import jax
import jax.numpy as jnp

_MAMBA = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_norm", "b_norm",
          "c_norm", "dt_proj", "dt_bias", "a_log", "d_skip", "out_proj")
_ATTENTION = ("q", "k", "v", "o")


def _leaf(key, name, shape, dtype):
    dtype = jnp.dtype(dtype)

    def uniform(lo, hi, dt=dtype):
        return jax.random.uniform(key, shape, dt, lo, hi)

    if name.endswith("a_log"):
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))[:, None],
            shape).astype(dtype)
    if name.endswith("dt_bias"):
        delta = jnp.exp(uniform(jnp.log(1e-3), jnp.log(1e-1), jnp.float32))
        return (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype)
    if name.endswith("d_skip"):
        return jnp.ones(shape, dtype)
    if name.endswith("norm"):
        return uniform(0.9, 1.1)
    if name.endswith("conv_b"):
        return uniform(-0.1, 0.1)
    var = (1.0 if name == "hsd_embed" else
           1.0 / shape[0] if name.endswith("conv_w") else 1.0 / shape[-2])
    a = (3.0 * var) ** 0.5
    return uniform(-a, a)


def make(cfg, seed, dtype="bfloat16"):
    """{program name: array} on the default device, from ``seed`` alone."""
    from paddle_tpu.models.hybrid_ssm_decoder import parameter_shapes

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
    for i in range(int(cfg["num_hidden_layers"])):
        def get(part, i=i):
            return named["hsd_%d_%s" % (i, part)]

        attention = "hsd_%d_q" % i in named
        layers.append({
            "in_norm": get("in_norm"), "ff_norm": get("ff_norm"),
            "ffn": {k: get("ffn_" + k) for k in ("gate", "up", "down")},
            "mixer": {k: get(k)
                      for k in (_ATTENTION if attention else _MAMBA)}})
    return {"embed": named["hsd_embed"],
            "final_norm": named["hsd_final_norm"], "layers": layers}
