"""Weights of the latent-attention decoder under learned sparse attention
(``glm_moe_dsa``) from the seed, made on the device in ONE jitted call, in
the type they are served in (bfloat16; the router's selection bias
float32), as ``weights_glm.py`` makes its family's.

``make`` returns ``{program name: array}``
(``paddle_tpu.models.latent_moe_decoder.parameter_shapes`` names them: the
HELD experts only, the router's every output, the vocabulary's slice);
``tree`` lays the SAME arrays out as the reference's nested tree
(``reference/sparse_latent_moe_decoder.py``), so both sides hold one copy.

Initialisers (seeded weights stand for a checkpoint; only their scale
matters): a matrix is uniform with variance 1 / fan-in, the embedding has
variance 1, a norm's scale is 1 +- 0.1, the indexer's LayerNorm shift is
uniform in +-0.1, the selection bias uniform in +-0.01. Uniform and not
normal: 16 random bits a bfloat16 element and no float32 temporary.
"""

import jax
import jax.numpy as jnp

_LAYER_KEYS = ("attn_norm", "q_a", "q_norm", "q_b", "kv_a", "kv_norm",
               "kv_b", "o", "ffn_norm")
_INDEXER = {"q": "idx_q", "k": "idx_k", "k_norm": "idx_k_norm",
            "k_shift": "idx_k_shift", "w": "idx_w"}
_DENSE = {"gate": "ffn_gate", "up": "ffn_up", "down": "ffn_down"}
_MOE = {"router": "router", "router_bias": "router_bias",
        "gate": "experts_gate", "up": "experts_up", "down": "experts_down",
        "shared_gate": "shared_gate", "shared_up": "shared_up",
        "shared_down": "shared_down"}


def _leaf(key, name, shape, dtype):
    dtype = jnp.dtype(dtype)
    if name.endswith("router_bias"):
        return jax.random.uniform(key, shape, dtype, -0.01, 0.01)
    if name.endswith("shift"):
        return jax.random.uniform(key, shape, dtype, -0.1, 0.1)
    if name.endswith("norm"):
        return jax.random.uniform(key, shape, dtype, 0.9, 1.1)
    var = 1.0 if name == "lmd_embed" else 1.0 / shape[-2]
    a = (3.0 * var) ** 0.5
    return jax.random.uniform(key, shape, dtype, -a, a)


def make(cfg, seed, dtype="bfloat16"):
    """{program name: array} on the default device, from ``seed`` alone."""
    from paddle_tpu.models.latent_moe_decoder import parameter_shapes

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
    dense = int(cfg.get("first_k_dense_replace", 0))
    layers = []
    for i in range(int(cfg["num_hidden_layers"])):
        def get(part, i=i):
            return named["lmd_%d_%s" % (i, part)]

        layer = {k: get(k) for k in _LAYER_KEYS}
        if "lmd_%d_idx_q" % i in named:
            layer["indexer"] = {k: get(v) for k, v in _INDEXER.items()}
        parts = _DENSE if i < dense else _MOE
        layer["ffn"] = {k: get(v) for k, v in parts.items()
                        if "lmd_%d_%s" % (i, v) in named}
        layers.append(layer)
    return {"embed": named["lmd_embed"], "head": named["lmd_head"],
            "final_norm": named["lmd_final_norm"], "layers": layers}
