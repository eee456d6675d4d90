"""The plain reference of a decoder-only model with sliding-window and full
attention layers and routed experts (HF ``afmoe``'s forward): float32
``jax.numpy`` at HIGHEST matmul precision, no kernels, no cache, no
batching. The window is an explicit band mask over the whole sequence;
the experts are a plain loop over all of them with a dense mask.

``x`` is ``[tokens, hidden]``, ``h0 = Embedding[ids] * sqrt(hidden)``
(``mup_enabled``). Block::

    a = RMSNorm(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg        (no bias)
    q, k = RMSNorm_q(q), RMSNorm_k(k)        over each head's ``head_dim``
    sliding layer: RoPE(q), RoPE(k) (rotate_half, every column), key p
                   visible to query t iff 0 <= t - p < sliding_window
    full layer:    NO positional encoding, key p visible iff p <= t
    o = (softmax(q k^T / sqrt(head_dim)) v * sigmoid(g)) Wo
    h = h + RMSNorm_post_attn(o)
    m = RMSNorm_pre_mlp(h)
    f = SwiGLU(m)                           in the first num_dense_layers
      | sum_e w_e SwiGLU_e(m) + SwiGLU_shared(m)   s = sigmoid(m Wr), the
        experts chosen by the largest s + b, w = s[chosen] / (sum + 1e-20)
        * route_scale
    h = h + RMSNorm_post_mlp(f)

then RMSNorm and the untied head.

Departures from the published code (the configuration's ``assumed`` lists
them): the attention softmax is taken a query head at a time and the head
a slice of the vocabulary at a time (the same arithmetic, so that 8192
positions and 200 192 logits fit beside the served model); dropout, the
load-balancing update of ``expert_bias`` and the auxiliary loss are
training's and are not here.

The parameter tree (``weights_trinity.py`` makes it; any float dtype,
upcast here a layer, and in it an expert, at a time)::

    {"embed" [V, D], "head" [D, V], "final_norm" [D],
     "layers": [{"attn_norm", "q", "k", "v", "gate", "q_norm", "k_norm",
                 "o", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm",
                 "ffn": {"gate", "up", "down"}            # a dense layer
                   or  {"router", "router_bias", "gate" [E, D, F], "up",
                        "down", "shared_gate", "shared_up", "shared_down"}}]}
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
SLIDING = "sliding_attention"


def dims(desc):
    return dict(
        D=desc["hidden_size"], H=desc["num_attention_heads"],
        Hkv=desc["num_key_value_heads"], dh=desc["head_dim"],
        W=desc["sliding_window"], eps=float(desc["rms_norm_eps"]),
        theta=float(desc["rope_theta"]), k=desc["num_experts_per_tok"],
        scale=float(desc["route_scale"]),
        norm_topk=bool(desc["route_norm"]))


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


def attention(p, x, d, sliding, band=True, mm=jnp.matmul):
    """Gated grouped-query attention over the whole sequence; ``band``
    False leaves a sliding layer's window out (a control: what a full
    cache in its place would compute)."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q = rms_norm(mm(x, p["q"]).reshape(T, d["H"], d["dh"]), p["q_norm"],
                 d["eps"])
    k = rms_norm(mm(x, p["k"]).reshape(T, d["Hkv"], d["dh"]), p["k_norm"],
                 d["eps"])
    v = mm(x, p["v"]).reshape(T, d["Hkv"], d["dh"])
    if sliding:
        q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
    behind = pos[:, None] - pos[None, :]                 # t - p
    visible = behind >= 0
    if sliding and band:
        visible = visible & (behind < d["W"])
    group = d["H"] // d["Hkv"]

    def one_head(j):
        s = mm(q[:, j], k[:, j // group].T) / jnp.sqrt(F32(d["dh"]))
        s = jnp.where(visible, s, -jnp.inf)
        return mm(jax.nn.softmax(s, -1), v[:, j // group])   # [T, dh]

    out = jax.lax.map(one_head, jnp.arange(d["H"]))           # [H, T, dh]
    out = jnp.transpose(out, (1, 0, 2)).reshape(T, -1)
    return mm(out * jax.nn.sigmoid(mm(x, p["gate"])), p["o"])


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


@functools.partial(jax.jit, static_argnums=(2, 3, 5, 6))
def layer(p, x, dkey, sliding, chosen=None, quant=None, band=True):
    """One block on float32 ``x``; ``p`` is upcast here (the experts one
    at a time). ``dkey``: ``dims`` as sorted items. ``quant`` rounds every
    matrix product's operands (the lower-precision control). Returns
    (y, s + b or None, the router's own choice or None)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    ffn = p["ffn"]
    p = {k: v.astype(F32) for k, v in p.items() if k != "ffn"}
    o = attention(p, rms_norm(x, p["attn_norm"], d["eps"]), d, sliding,
                  band, mm)
    h = x + rms_norm(o, p["post_attn_norm"], d["eps"])
    m = rms_norm(h, p["pre_mlp_norm"], d["eps"])
    biased = own = None
    if "router" in ffn:
        f, biased, own = experts_ffn(ffn, m, d, chosen, mm)
    else:
        f = swiglu(m, *(ffn[k].astype(F32) for k in ("gate", "up", "down")),
                   mm=mm)
    return h + rms_norm(f, p["post_mlp_norm"], d["eps"]), biased, own


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, w, eps, quant=None):
    """RMSNorm and the head, a slice of the vocabulary at a time (the
    float32 copy of a 200 192-wide head is 1.6 GB)."""
    x = rms_norm(x, norm.astype(F32), eps)
    V = w.shape[1]
    parts = next(n for n in (8, 4, 2, 1) if V % n == 0)

    def one(i):
        cols = jax.lax.dynamic_slice_in_dim(w, i * (V // parts), V // parts,
                                            axis=1).astype(F32)
        return x @ cols if quant is None else quant(x) @ quant(cols)

    out = jax.lax.map(one, jnp.arange(parts))                 # [parts, n, v]
    return jnp.transpose(out, (1, 0, 2)).reshape(x.shape[0], V)


def forward(params, tokens, desc, chosen=None, logits_at=None, quant=None,
            band=True):
    """The full forward over one sequence ``tokens`` [T].

    ``chosen``: per layer, None or ``[T, k]`` expert ids to use in place
    of the router's own choice (the program's, for the comparison under
    its choice). ``logits_at``: the positions whose logits are returned
    (all when None). ``quant``: a function that rounds the operands of
    every matrix product (the control one precision down; None is
    float32). ``band`` False: the sliding layers attend every earlier
    position (the control that a missing window must fail).
    Returns ``{"logits" [n, V], "biased": [per expert layer, [T, E]],
    "own": [per expert layer, [T, k]]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        if desc.get("mup_enabled", False):
            x = x * jnp.sqrt(F32(d["D"]))
        biased, own = [], []
        for i, p in enumerate(params["layers"]):
            x, b, o = layer(p, x, dkey, desc["layer_types"][i] == SLIDING,
                            None if chosen is None else chosen[i], quant,
                            band)
            if b is not None:
                biased.append(b)
                own.append(o)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        logits = head(x, params["final_norm"], params["head"], d["eps"],
                      quant)
    return {"logits": logits, "biased": biased, "own": own}
