"""Weights from the seed, made on the device in ONE jitted call.

The tree is the reference's (``reference/transformer.py``); ``NAME_MAP``
says under which name the program keeps each leaf. The benchmark makes the
weights and hands the same arrays to both sides, so the reference takes
nothing the program has made.
"""

import jax
import jax.numpy as jnp


def _shapes(cfg):
    """{reference path: (shape, kind)}; kind picks the initialiser."""
    D, F = cfg["d_model"], cfg["d_inner"]
    out = {("src_emb",): ((cfg["src_vocab_size"], D), "emb"),
           ("trg_emb",): ((cfg["trg_vocab_size"], D), "emb"),
           ("proj_w",): ((D, cfg["trg_vocab_size"]), "mat"),
           ("proj_b",): ((cfg["trg_vocab_size"],), "bias")}

    def ln(path):
        out[path + (0,)] = ((D,), "scale")
        out[path + (1,)] = ((D,), "bias")

    def attn(path):
        for p in "qkvo":
            out[path + (p,)] = ((D, D), "mat")

    def ffn(path):
        out[path + ("w1",)] = ((D, F), "mat")
        out[path + ("b1",)] = ((F,), "bias")
        out[path + ("w2",)] = ((F, D), "mat")
        out[path + ("b2",)] = ((D,), "bias")

    for i in range(cfg["n_layer"]):
        ln(("enc", i, "attn_ln")), attn(("enc", i, "attn"))
        ln(("enc", i, "ffn_ln")), ffn(("enc", i, "ffn"))
        ln(("dec", i, "self_ln")), attn(("dec", i, "self"))
        ln(("dec", i, "cross_ln")), attn(("dec", i, "cross"))
        ln(("dec", i, "ffn_ln")), ffn(("dec", i, "ffn"))
    ln(("enc_final_ln",)), ln(("dec_final_ln",))
    return out


_ATTN = {"attn": "mha", "self": "smha", "cross": "cmha"}
_LN = {"attn_ln": "attn_ln", "ffn_ln": "ffn_ln", "self_ln": "sattn_ln",
       "cross_ln": "cattn_ln"}
_FFN = {"w1": "ffn_fc1.w_0", "b1": "ffn_fc1.w_1", "w2": "ffn_fc2.w_0",
        "b2": "ffn_fc2.w_1"}


def program_name(path):
    """The program's parameter name of one reference leaf."""
    if path[0] in ("src_emb", "trg_emb"):
        return path[0]
    if path[0] == "proj_w":
        return "proj_logits.w_0"
    if path[0] == "proj_b":
        return "proj_logits.w_1"
    if path[0] in ("enc_final_ln", "dec_final_ln"):
        return "%s.w_%d" % (path[0], path[1])
    stack, i, part = path[0], path[1], path[2]
    if part in _LN:
        return "%s_%d_%s.w_%d" % (stack, i, _LN[part], path[3])
    if part in _ATTN:
        return "%s_%d_%s_%s.w_0" % (stack, i, _ATTN[part], path[3])
    return "%s_%d_%s" % (stack, i, _FFN[path[3]])


def _nest(flat):
    """{path: leaf} -> the reference's nested tree (lists for stacks and
    LayerNorm pairs)."""
    root = {}
    for path, leaf in flat.items():
        node = root
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf

    def lists(node):
        if not isinstance(node, dict):
            return node
        if node and all(isinstance(k, int) for k in node):
            return [lists(node[k]) for k in sorted(node)]
        return {k: lists(v) for k, v in node.items()}

    return lists(root)


def make(cfg, seed):
    """(nested reference tree, {program name: array}); float32, on the
    default device, from ``seed`` alone."""
    shapes = _shapes(cfg)
    paths = sorted(shapes, key=str)

    def build(key):
        flat = {}
        for n, path in enumerate(paths):
            shape, kind = shapes[path]
            k = jax.random.fold_in(key, n)
            if kind == "mat":
                leaf = jax.random.normal(k, shape) * shape[0] ** -0.5
            elif kind == "emb":
                leaf = jax.random.normal(k, shape) * shape[1] ** -0.5
            elif kind == "scale":
                leaf = 1.0 + 0.1 * jax.random.normal(k, shape)
            else:
                leaf = 0.02 * jax.random.normal(k, shape)
            flat[path] = leaf.astype(jnp.float32)
        return flat

    # seeds run past 2**31: fold the high bits in instead of truncating
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    flat = jax.jit(build)(key)
    return _nest(flat), {program_name(p): v for p, v in flat.items()}


def install(named, scope):
    """Overwrite the scope's parameters (made by the startup program) with
    the benchmark's. Every name must already exist with that shape."""
    for name, value in named.items():
        var = scope.find_var(name)
        if var is None or var.value is None:
            raise KeyError("the program has no parameter %r" % name)
        if tuple(var.value.shape) != tuple(value.shape):
            raise ValueError("%s: program %s, benchmark %s" % (
                name, tuple(var.value.shape), tuple(value.shape)))
        var.set(value)
