"""The plain reference of a decoder-only model with latent (MLA)
attention and routed experts (HF ``glm4_moe_lite``'s forward): float32
``jax.numpy`` at HIGHEST matmul precision, no kernels, no cache, no
batching. Attention is computed in the EXPANDED form only (every
position's per-head keys and values are made from its compressed row), so
the program's absorbed-form decode is checked against independent
arithmetic; the experts are a plain loop over all of them with a dense
mask.

``x`` is ``[tokens, hidden]``. Block: ``h = x + MLA(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``; after the last block RMSNorm, then the
untied head. The first ``first_k_dense_replace`` layers have a SwiGLU FFN;
the others ``Shared(x) + scale * sum_i w_i Expert_i(x)`` with
``s = sigmoid(float32(x) float32(Wr))``, the experts chosen by the largest
``s + b``, ``w = s[chosen] / (sum s[chosen] + 1e-20)``.

Departures from the published forward (the configuration's ``assumed``
lists them): the multi-token-prediction module is not loaded; RoPE pairs
the rotary columns in split halves (``rotate_half``).

The parameter tree (``weights_glm.py`` makes it; any float dtype, upcast
here a layer at a time so that the published widths fit one chip)::

    {"embed" [V, D], "head" [D, V], "final_norm" [D],
     "layers": [{"attn_norm", "q_a", "q_norm", "q_b", "kv_a", "kv_norm",
                 "kv_b", "o", "ffn_norm",
                 "ffn": {"gate", "up", "down"}            # a dense layer
                   or  {"router", "router_bias", "gate" [E, D, F], "up",
                        "down", "shared_gate", "shared_up", "shared_down"}}]}
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(desc):
    return dict(
        D=desc["hidden_size"], H=desc["num_attention_heads"],
        dn=desc["qk_nope_head_dim"], dr=desc["qk_rope_head_dim"],
        dv=desc["v_head_dim"], C=desc["kv_lora_rank"],
        eps=float(desc["rms_norm_eps"]), theta=float(desc["rope_theta"]),
        k=desc["num_experts_per_tok"],
        scale=float(desc["routed_scaling_factor"]),
        norm_topk=bool(desc["norm_topk_prob"]))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """``x`` [T, ..., d], rotary pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def swiglu(x, gate, up, down, mm=jnp.matmul):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def attention(p, x, d, mm=jnp.matmul):
    """Causal MLA over the whole sequence, expanded form."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q = mm(rms_norm(mm(x, p["q_a"]), p["q_norm"], d["eps"]), p["q_b"])
    q = q.reshape(T, d["H"], d["dn"] + d["dr"])
    q_nope, q_rope = q[..., :d["dn"]], rope(q[..., d["dn"]:], pos,
                                             d["theta"])
    kva = mm(x, p["kv_a"])
    ckv = rms_norm(kva[:, :d["C"]], p["kv_norm"], d["eps"])
    k_rope = rope(kva[:, d["C"]:], pos, d["theta"])          # [T, dr]
    kv = mm(ckv, p["kv_b"]).reshape(T, d["H"], d["dn"] + d["dv"])
    k_nope, v = kv[..., :d["dn"]], kv[..., d["dn"]:]
    s = (jnp.einsum("thn,shn->hts", q_nope, k_nope)
         + jnp.einsum("thr,sr->hts", q_rope, k_rope))
    s = s / jnp.sqrt(F32(d["dn"] + d["dr"]))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    out = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v)
    return mm(out.reshape(T, -1), p["o"])


def route(p, x, d, mm=jnp.matmul):
    """(the router's ``s`` [T, E], ``s + b`` [T, E], its own choice
    [T, k])."""
    s = jax.nn.sigmoid(mm(x, p["router"].astype(F32)))
    biased = s + p["router_bias"].astype(F32)
    return s, biased, jax.lax.top_k(biased, d["k"])[1]


def experts_ffn(p, x, d, chosen=None, mm=jnp.matmul):
    """``Shared(x) + scale * sum_i w_i Expert_i(x)`` over ``chosen`` (the
    router's own choice when None); every expert is computed for every
    token, one at a time (upcast as it is used), and the others are masked
    out. Returns (out, s + b, the router's own choice)."""
    s, biased, own = route(p, x, d, mm)
    use = own if chosen is None else chosen
    w = jnp.take_along_axis(s, use, -1)
    if d["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    E = s.shape[1]
    # [T, E] weight of each expert for each token (0 where not chosen)
    dense = jnp.sum(jax.nn.one_hot(use, E, dtype=F32) * w[..., None], 1)

    def one(acc, ew):
        gate, up, down, col = ew
        y = swiglu(x, gate.astype(F32), up.astype(F32), down.astype(F32),
                   mm)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], dense.T))
    out = d["scale"] * routed
    if "shared_gate" in p:
        out = out + swiglu(x, p["shared_gate"].astype(F32),
                           p["shared_up"].astype(F32),
                           p["shared_down"].astype(F32), mm)
    return out, biased, own


@functools.partial(jax.jit, static_argnums=(2, 4))
def layer(p, x, dkey, chosen=None, quant=None):
    """One block on float32 ``x``; ``p`` is upcast here (the experts one
    at a time). ``dkey``: ``dims`` as sorted items. ``quant`` rounds every
    matrix product's operands (the lower-precision control). Returns
    (y, s + b or None, the router's own choice or None)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    ffn = p["ffn"]
    p = {k: v.astype(F32) for k, v in p.items() if k != "ffn"}
    h = x + attention(p, rms_norm(x, p["attn_norm"], d["eps"]), d, mm)
    nx = rms_norm(h, p["ffn_norm"], d["eps"])
    if "router" in ffn:
        out, biased, own = experts_ffn(ffn, nx, d, chosen, mm)
        return h + out, biased, own
    f = {k: v.astype(F32) for k, v in ffn.items()}
    return h + swiglu(nx, f["gate"], f["up"], f["down"], mm), None, None


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, w, eps, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    w = w.astype(F32)
    return x @ w if quant is None else quant(x) @ quant(w)


def forward(params, tokens, desc, chosen=None, logits_at=None, quant=None):
    """The full forward over one sequence ``tokens`` [T].

    ``chosen``: per layer, None or ``[T, k]`` expert ids to use in place
    of the router's own choice (the program's, for the comparison under
    its choice). ``logits_at``: the positions whose logits are returned
    (all when None). ``quant``: a function that rounds the operands of
    every matrix product (the control one precision down; None is float32).
    Returns ``{"logits" [n, V], "biased": [per expert layer, [T, E]],
    "own": [per expert layer, [T, k]]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        biased, own = [], []
        for i, p in enumerate(params["layers"]):
            x, b, o = layer(p, x, dkey,
                            None if chosen is None else chosen[i], quant)
            if b is not None:
                biased.append(b)
                own.append(o)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        logits = head(x, params["final_norm"], params["head"], d["eps"],
                      quant)
    return {"logits": logits, "biased": biased, "own": own}
