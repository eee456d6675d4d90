"""Weights of the shortcut decoder (two latent-attention blocks and two
dense feed-forwards a layer, the expert block across them; HF
``longcat_flash``) from the seed, made on the device in ONE jitted call,
in the type they are served in (bfloat16; the router's selection bias
float32), as ``weights_glm.py`` makes its family's.

``make`` returns ``{program name: array}``
(``paddle_tpu.models.shortcut_moe_decoder.parameter_shapes`` names them:
the HELD experts only, the router's every output, identities among them,
the vocabulary's slice); ``tree`` lays the SAME arrays out as the
reference's nested tree (``reference/shortcut_moe_decoder.py``), so both
sides hold one copy.

Initialisers (seeded weights stand for a checkpoint; only their scale
matters): a matrix is uniform with variance 1 / fan-in, the embedding has
variance 1, a norm's scale is 1 +- 0.1, the selection bias uniform in
+-0.01, and the ROUTER has variance ``ROUTER_GAIN^2 / fan-in``: a token's
768 logits then have a standard deviation near 4 and the 12 chosen hold
more than half of the probability at the median token, as a trained
router's do (at unit gain every weight is 6 / 768, the expert block
vanishes from the logits and the check with it; the configuration's
``assumed``). The two up-projections whose outputs the model RESCALES
(``q_b`` under ``mla_scale_q_lora``, ``kv_b`` under ``mla_scale_kv_lora``)
are seeded ``1 / scale`` smaller: the program and the reference both apply
the published factors 2.0 and 3.4641, and the seeded scores then have the
magnitude an unscaled seeded model's have. Seeded at unit variance the
factors make every score 7 x larger, attention all but an argmax, and the
reference itself moves 17% when its operands are rounded to bfloat16 (my
chip run, PR 49: no check can tell a program from a fault through that).
Uniform and not normal: 16 random bits a bfloat16 element and no float32
temporary.
"""

import jax
import jax.numpy as jnp

ROUTER_GAIN = 4.0
_SUB = ("attn_norm", "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o",
        "ffn_norm", "ffn_gate", "ffn_up", "ffn_down")
_MOE = {"router": "router", "router_bias": "router_bias",
        "gate": "experts_gate", "up": "experts_up", "down": "experts_down"}


def _gains(cfg):
    """{the last part of a matrix's name: the factor on its seeded
    standard deviation}: the router's gain, and ``1 / scale`` for the two
    up-projections whose outputs the model rescales."""
    D = float(cfg["hidden_size"])
    gains = {"router": ROUTER_GAIN}
    if cfg.get("mla_scale_q_lora"):
        gains["q_b"] = (cfg["q_lora_rank"] / D) ** 0.5
    if cfg.get("mla_scale_kv_lora"):
        gains["kv_b"] = (cfg["kv_lora_rank"] / D) ** 0.5
    return gains


def _leaf(key, name, shape, dtype, gains):
    dtype = jnp.dtype(dtype)
    if name.endswith("router_bias"):
        return jax.random.uniform(key, shape, dtype, -0.01, 0.01)
    if name.endswith("norm"):
        return jax.random.uniform(key, shape, dtype, 0.9, 1.1)
    var = 1.0 if name == "scd_embed" else 1.0 / shape[-2]
    for part, gain in gains.items():
        if name.endswith("_" + part):
            var *= gain ** 2
    a = (3.0 * var) ** 0.5
    return jax.random.uniform(key, shape, dtype, -a, a)


def make(cfg, seed, dtype="bfloat16"):
    """{program name: array} on the default device, from ``seed`` alone."""
    from paddle_tpu.models.shortcut_moe_decoder import parameter_shapes

    shapes = parameter_shapes(cfg, dtype)
    names = list(shapes)
    gains = _gains(cfg)

    def build(key):
        return {name: _leaf(jax.random.fold_in(key, n), name,
                            *shapes[name], gains)
                for n, name in enumerate(names)}

    # seeds run past 2**31: fold the high bits in instead of truncating
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def tree(named, cfg):
    """The reference's nested tree over the same arrays."""
    layers = []
    for i in range(int(cfg["num_layers"])):
        layers.append({
            "sub": [{k: named["scd_%d_%d_%s" % (i, a, k)] for k in _SUB}
                    for a in (0, 1)],
            "moe": {k: named["scd_%d_%s" % (i, v)]
                    for k, v in _MOE.items()}})
    return {"embed": named["scd_embed"], "head": named["scd_head"],
            "final_norm": named["scd_final_norm"], "layers": layers}
