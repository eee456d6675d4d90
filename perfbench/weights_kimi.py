"""Weights of the delta-rule linear-attention / latent-attention decoder
with a leading dense layer and routed experts (``kimi_linear``) from the
seed, made on the device in ONE jitted call, in the type they are served
in (bfloat16; the router's selection bias, ``a_log`` and ``dt_bias``
float32), with ``weights_solar.py``'s initialisers as they are (its
``_leaf``: the configuration's ``assumed`` lists them).

``make`` returns ``{program name: array}``
(``paddle_tpu.models.linear_attn_moe_decoder.parameter_shapes`` names
them: the HELD experts only, the router's every output, the vocabulary's
slice); ``tree`` lays the SAME arrays out as the reference's nested tree
(``reference/linear_latent_moe_decoder.py``), so both sides hold one copy.
"""

from perfbench import weights_solar

_LINEAR = weights_solar._LINEAR
_LATENT = ("q", "kv_a", "kv_norm", "kv_b", "o")
_DENSE = {"gate": "ffn_gate", "up": "ffn_up", "down": "ffn_down"}
_MOE = weights_solar._MOE

# the same names, the same initialisers, the same fold of a large seed
make = weights_solar.make


def tree(named, cfg):
    """The reference's nested tree over the same arrays."""
    layers = []
    for i in range(int(cfg["num_hidden_layers"])):
        def get(part, i=i):
            return named["lad_%d_%s" % (i, part)]

        def has(part, i=i):
            return "lad_%d_%s" % (i, part) in named

        layers.append({
            "in_norm": get("in_norm"), "ff_norm": get("ff_norm"),
            "mixer": {k: get(k)
                      for k in (_LATENT if has("kv_a") else _LINEAR)},
            "ffn": {k: get(v) for k, v in
                    (_MOE if has("router") else _DENSE).items() if has(v)}})
    return {"embed": named["lad_embed"], "head": named["lad_head"],
            "final_norm": named["lad_final_norm"], "layers": layers}
