"""The plain reference of a DENSE decoder-only model of Gated DeltaNet
linear-attention layers (arXiv:2412.06464; flash-linear-attention's
``GatedDeltaNet`` module, the layer HF ``qwen3_next`` names by the same
``linear_*`` keys) with a few multi-head attention layers among them, in
the Olmo family's post-norm blocks (HF ``olmo_hybrid``): float32
``jax.numpy`` at HIGHEST matmul precision, no kernels, no cache, no chunks,
no batching, the recurrence a plain loop over ``t``. It imports no module
of the program.

``x`` is ``[tokens, hidden]``; a sub-block reads it as it is (no input
norm) and its OUTPUT is normed before it joins the residual stream.

    block:   h = x + RMSNorm_a(Mixer_i(x));   y = h + RMSNorm_f(SwiGLU(h))
    SwiGLU:  (silu(x Wg) * (x Wu)) Wd
    linear layer (H heads, key width dk, value width dv):
      q, k, v  = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
                 (causal, depthwise, ``linear_conv_kernel_dim`` taps)
      q <- q / sqrt(|q|^2 + 1e-6) * dk^-1/2,  k <- k / sqrt(|k|^2 + 1e-6)
      g_t      = -exp(A_log_h) * softplus(x Wa + dt_bias_h)   [H]: a HEAD
      beta_t   = 2 sigmoid(x Wb)  (``linear_allow_neg_eigval``)   [H]
      S'       = exp(g_t) S_{t-1}               (S [dk, dv], S_0 = 0)
      w        = beta_t (v_t - S'^T k_t)
      S_t      = S' + k_t w^T;      o_t = S_t^T q_t
      out      = (RMSNorm_head(o_t) * silu(x Wgate)) Wo
    full layer: q = RMSNorm_q(x Wq), k = RMSNorm_k(x Wk) over the WHOLE
      projected row, v = x Wv; NO positional encoding (``rotate`` is the
      control that applies one); causal softmax at head_dim^-1/2,
      multi-head; out = attn Wo
    logits = RMSNorm_final(y_L) @ W_head

What ``config.json`` does not carry (the norms' placement, the q/k norm,
the absence of a rotation, the initialisers) is the configuration's
``assumed``. The PARAMETERS' layout is the served program's, so that both
sides hold one copy (``weights_olmo.tree``): every matrix is ``[in,
out]``, a linear layer's three projections are one ``q | k | v`` matrix
and its three convolutions one ``[taps, q | k | v]`` weight.

The parameter tree (any float dtype, upcast here a layer at a time)::

    {"embed" [V, D], "head" [D, V], "final_norm" [D],
     "layers": [{"attn_norm", "ff_norm",
                 "mixer": {"q", "k", "v", "q_norm", "k_norm", "o"}  # full
                   or     {"qkv", "conv_w", "a", "dt_bias", "a_log",
                           "beta", "gate", "o_norm", "o"},
                 "ffn": {"gate", "up", "down"}}]}
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512     # queries a block of attention
L2_EPS = 1e-6


def dims(desc):
    D, H = desc["hidden_size"], desc["num_attention_heads"]
    return dict(
        D=D, H=H, dh=D // H, Hl=desc["linear_num_key_heads"],
        dk=desc["linear_key_head_dim"], dv=desc["linear_value_head_dim"],
        kw=desc["linear_conv_kernel_dim"], eps=float(desc["rms_norm_eps"]),
        beta_scale=2.0 if desc["linear_allow_neg_eigval"] else 1.0)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def rope(x, theta):
    """The split-halves rotation of ``x`` [T, H, dh] at positions 0..T-1
    (the control: the model has none)."""
    T, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def attention(p, x, d, mm, rotate=None):
    """Causal multi-head attention over the whole sequence, q and k normed
    over the whole projected row, a block of queries at a time."""
    T, H, dh = x.shape[0], d["H"], d["dh"]
    pos = jnp.arange(T)
    q = rms_norm(mm(x, p["q"]), p["q_norm"], d["eps"]).reshape(T, H, dh)
    k = rms_norm(mm(x, p["k"]), p["k_norm"], d["eps"]).reshape(T, H, dh)
    v = mm(x, p["v"]).reshape(T, H, dh)
    if rotate is not None:
        q, k = rope(q, rotate), rope(k, rotate)
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        at = pos[lo:lo + QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", q[lo:lo + QUERY_BLOCK], k) \
            / jnp.sqrt(F32(dh))
        s = jnp.where(pos[None, None, :] <= at[None, :, None], s, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v))
    return mm(jnp.concatenate(out).reshape(T, -1), p["o"])


def delta_rule(q, k, v, g, beta, record_at, state_round=None):
    """The recurrence over the whole sequence: q, k [T, H, dk] (both
    normalised), v [T, H, dv], g and beta [T, H]. Returns (o [T, H, dv],
    S [len(record_at), H, dk, dv] after each of the positions
    ``record_at``)."""
    def token(carry, t):
        s, kept = carry
        sp = jnp.exp(g[t])[:, None, None] * s
        w = beta[t][:, None] * (v[t] - jnp.einsum("hkv,hk->hv", sp, k[t]))
        s = sp + k[t][:, :, None] * w[:, None, :]
        if state_round is not None:
            s = state_round(s)
        kept = jnp.where((record_at == t)[:, None, None, None], s[None],
                         kept)
        return (s, kept), jnp.einsum("hkv,hk->hv", s, q[t])

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    start = (jnp.zeros((H, dk, dv), F32),
             jnp.zeros((record_at.shape[0], H, dk, dv), F32))
    (_s, kept), o = jax.lax.scan(token, start, jnp.arange(q.shape[0]))
    return o, kept


def linear_attention(p, x, d, record_at, mm, state_round=None):
    """The Gated DeltaNet mixer over the whole sequence ``x`` [T, D].
    Returns (out [T, D], S [len(record_at), H, dk, dv])."""
    T, H, dk, dv, kw = x.shape[0], d["Hl"], d["dk"], d["dv"], d["kw"]
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(mm(x, p["a"])
                                               + p["dt_bias"])    # [T, H]
    beta = d["beta_scale"] * jax.nn.sigmoid(mm(x, p["beta"]))     # [T, H]
    xp = jnp.pad(mm(x, p["qkv"]), ((kw - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][j] * xp[j:j + T] for j in range(kw)))
    q = qkv[:, :H * dk].reshape(T, H, dk)
    k = qkv[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    o, kept = delta_rule(l2_normalise(q) * dk ** -0.5, l2_normalise(k), v,
                         g, beta, record_at, state_round)
    o = rms_norm(o, p["o_norm"], d["eps"]).reshape(T, H * dv)
    return mm(o * jax.nn.silu(mm(x, p["gate"])), p["o"]), kept


@functools.partial(jax.jit, static_argnums=(2, 3, 5, 6, 7))
def layer(p, x, dkey, kind, record_at, quant=None, state_round=None,
          rotate=None):
    """One block on float32 ``x``; ``p`` is upcast here. ``quant`` rounds
    every matrix product's operands, ``state_round`` the state after every
    token, ``rotate`` is a theta at which a full layer's q and k are
    rotated (the controls). Returns (y, S or None)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    mixer = {k: v.astype(F32) for k, v in p["mixer"].items()}
    ffn = {k: v.astype(F32) for k, v in p["ffn"].items()}
    if kind == "full_attention":
        out, kept = attention(mixer, x, d, mm, rotate), None
    else:
        out, kept = linear_attention(mixer, x, d, record_at, mm,
                                     state_round)
    h = x + rms_norm(out, p["attn_norm"].astype(F32), d["eps"])
    ff = mm(jax.nn.silu(mm(h, ffn["gate"])) * mm(h, ffn["up"]), ffn["down"])
    return h + rms_norm(ff, p["ff_norm"].astype(F32), d["eps"]), kept


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, w, eps, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    w = w.astype(F32)
    return x @ w if quant is None else quant(x) @ quant(w)


def forward(params, tokens, desc, logits_at=None, states_at=(), quant=None,
            state_round=None, rotate=None):
    """The full forward over one sequence ``tokens`` [T].

    ``logits_at``: the positions whose logits are returned (all when
    None). ``states_at``: positions after which every linear layer's ``S``
    is returned. ``quant``: a function that rounds the operands of every
    matrix product, ``state_round`` one that rounds ``S`` after every
    token, ``rotate`` a theta at which the full layers' q and k are
    rotated (the controls; None is the model). Returns ``{"logits" [n, V],
    "states": [per linear layer, [len(states_at), H, dk, dv]]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    record_at = jnp.asarray(list(states_at) or [0], jnp.int32)
    states = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for p, kind in zip(params["layers"], desc["layer_types"]):
            x, kept = layer(p, x, dkey, kind, record_at, quant,
                            state_round, rotate)
            if kept is not None:
                states.append(kept)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        logits = head(x, params["final_norm"], params["head"], d["eps"],
                      quant)
    return {"logits": logits, "states": states}
