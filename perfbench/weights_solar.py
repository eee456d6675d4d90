"""Weights of the delta-rule linear-attention / grouped-query decoder with
routed experts (``solar_open2``) from the seed, made on the device in ONE
jitted call, in the type they are served in (bfloat16; the router's
selection bias, ``a_log`` and ``dt_bias`` float32), as ``weights_glm52.py``
makes its family's.

``make`` returns ``{program name: array}``
(``paddle_tpu.models.linear_attn_moe_decoder.parameter_shapes`` names
them: the HELD experts only, the router's every output, the vocabulary's
slice); ``tree`` lays the SAME arrays out as the reference's nested tree
(``reference/linear_attn_moe_decoder.py``), so both sides hold one copy.

Initialisers (seeded weights stand for a checkpoint; the configuration's
``assumed`` lists them): a matrix is uniform with variance 1 / fan-in, the
embedding has variance 1, a norm's scale is 1 +- 0.1, the convolution's
weight has variance 1 / taps, the output gate's bias is uniform in +-0.1,
the selection bias uniform in +-0.01; ``a_log = log(U(1, 16))`` a head and
``dt_bias`` the inverse softplus of a log-uniform 1e-3..1e-1 (the public
KDA initialisers): a state that neither dies in one token nor never
forgets.
"""

import jax
import jax.numpy as jnp

_LINEAR = ("qkv", "conv_w", "f_a", "f_b", "dt_bias", "a_log", "beta", "g_a",
           "g_b", "g_bias", "o_norm", "o")
_GQA = ("q", "k", "v", "gate", "o")
_MOE = {"router": "router", "router_bias": "router_bias",
        "gate": "experts_gate", "up": "experts_up", "down": "experts_down",
        "shared_gate": "shared_gate", "shared_up": "shared_up",
        "shared_down": "shared_down"}


def _leaf(key, name, shape, dtype):
    dtype = jnp.dtype(dtype)

    def uniform(lo, hi, dt=dtype):
        return jax.random.uniform(key, shape, dt, lo, hi)

    if name.endswith("router_bias"):
        return uniform(-0.01, 0.01)
    if name.endswith("a_log"):
        return jnp.log(uniform(1.0, 16.0))
    if name.endswith("dt_bias"):
        delta = jnp.exp(uniform(jnp.log(1e-3), jnp.log(1e-1), jnp.float32))
        return (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype)
    if name.endswith("g_bias"):
        return uniform(-0.1, 0.1)
    if name.endswith("norm"):
        return uniform(0.9, 1.1)
    var = (1.0 if name == "lad_embed" else
           1.0 / shape[0] if name.endswith("conv_w") else 1.0 / shape[-2])
    a = (3.0 * var) ** 0.5
    return uniform(-a, a)


def make(cfg, seed, dtype="bfloat16"):
    """{program name: array} on the default device, from ``seed`` alone."""
    from paddle_tpu.models.linear_attn_moe_decoder import parameter_shapes

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
            return named["lad_%d_%s" % (i, part)]

        gqa = "lad_%d_gate" % i in named
        layers.append({
            "in_norm": get("in_norm"), "ff_norm": get("ff_norm"),
            "mixer": {k: get(k) for k in (_GQA if gqa else _LINEAR)},
            "ffn": {k: get(v) for k, v in _MOE.items()
                    if "lad_%d_%s" % (i, v) in named}})
    return {"embed": named["lad_embed"], "head": named["lad_head"],
            "final_norm": named["lad_final_norm"], "layers": layers}
