"""The plain reference of a hybrid decoder-only model of Mamba-2 layers
(state-space duality, arXiv:2405.21060) with a few grouped-query attention
layers among them, routed experts beside a shared expert in every layer,
and multipliers on the embedding, the residual branches, the attention
scores and the logits (HF ``granitemoehybrid``): float32 ``jax.numpy`` at
HIGHEST matmul precision, no kernels, no cache, no chunks, no batching, the
recurrence a plain loop over ``t``.

``x`` is ``[tokens, hidden]``; ``u`` a mixer's normed input.

    x_0     = Embedding[ids] * embedding_multiplier
    block:    h = x + residual_multiplier * Mixer_i(RMSNorm_in(x))
              y = h + residual_multiplier * (Experts(n) + Shared(n)),
              n = RMSNorm_ff(h)
    Experts:  l = n W_r over ALL the routed experts; the top-k of l; g =
              softmax over the k chosen logits; sum_{i chosen and HELD}
              g_i E_i(n); E_i = down_i(silu(gate_i(n)) * up_i(n))
    Shared:   down(silu(gate(n)) * up(n)), added once
    Mamba-2 layer (H heads of P, a state of N, ONE group):
      z, xBC, dt = u W_z, u W_xbc, u W_dt
      xBC      = silu(conv(xBC) + b)     (causal, depthwise, d_conv taps)
      x, B, C  = xBC                     x [H, P], B [N], C [N]
      Delta_t  = softplus(dt_t + dt_bias)                          [H]
      s_t      = exp(Delta_t A) s_{t-1} + Delta_t x_t (x) B_t,  A = -exp(A_log)
                 (s [H, P, N], s_0 = 0)
      y_t      = s_t C_t + D x_t
      out      = (RMSNorm(y * silu(z)) * w) W_out   the gate BEFORE the norm,
                                                    one norm over H P
    attention layer: q, k, v = u Wq, u Wk, u Wv; NO positional encoding;
      causal softmax of scores * attention_multiplier, a group of query
      heads a key/value head; out = attn Wo
    logits = (RMSNorm_final(x_L) @ Embedding^T) / logits_scaling

Departures from the published forward: none in the mathematics (HF clamps
``Delta`` to ``time_step_limit``, by default (0, inf): no clamp). What
``config.json`` does not carry (the initialisers) is the configuration's
``assumed``. The PARAMETERS' layout is the served program's, so that both
sides hold one copy (``weights_granite.tree``): every matrix is ``[in,
out]`` and HF's one ``in_proj`` is its three column blocks. The same HELD
shard of the experts and slice of the vocabulary as the served model: the
rest is other chips'.

The parameter tree (any float dtype, upcast here a layer, and an expert,
at a time)::

    {"embed" [V, D], "final_norm" [D],
     "layers": [{"in_norm", "ff_norm",
                 "mixer": {"q", "k", "v", "o"}                # attention
                   or     {"in_z", "in_xbc", "in_dt", "conv_w", "conv_b",
                           "dt_bias", "a_log", "d_skip", "mix_norm",
                           "out_proj"},
                 "ffn": {"router", "gate", "up", "down", "shared_gate",
                         "shared_up", "shared_down"}}]}
"""

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.sparse_latent_moe_decoder import (
    _blocks,
    rms_norm,
    shared_part,
    swiglu,
)

F32 = jnp.float32
QUERY_BLOCK = 512     # queries a block of attention
HEAD_GROUP = 16       # heads of a Mamba-2 layer walked at a time
VOCAB_BLOCK = 8192    # rows of the tied table a block of the head


def dims(desc):
    shard = desc.get("expert_shard")
    D, H = desc["hidden_size"], desc["num_attention_heads"]
    dh = desc.get("head_dim") or D // H
    return dict(
        D=D, H=H, Hkv=desc["num_key_value_heads"], dh=dh,
        Hm=desc["mamba_n_heads"], P=desc["mamba_d_head"],
        n=desc["mamba_d_state"], kw=desc["mamba_d_conv"],
        eps=float(desc["rms_norm_eps"]), k=desc["num_experts_per_tok"],
        first=int(shard["first"]) if shard else 0,
        emb=float(desc.get("embedding_multiplier", 1.0)),
        res=float(desc.get("residual_multiplier", 1.0)),
        att=float(desc.get("attention_multiplier", dh ** -0.5)),
        logit=float(desc.get("logits_scaling", 1.0)))


def layer_kinds(desc):
    return list(desc["layer_types"])


def attention(p, u, d, mm=jnp.matmul):
    """Causal grouped-query attention over the whole sequence, no
    positional encoding, the scores times ``attention_multiplier``, a block
    of queries at a time."""
    T = u.shape[0]
    g = d["H"] // d["Hkv"]
    pos = jnp.arange(T)
    q = mm(u, p["q"]).reshape(T, d["Hkv"], g, d["dh"])
    k = mm(u, p["k"]).reshape(T, d["Hkv"], d["dh"])
    v = mm(u, p["v"]).reshape(T, d["Hkv"], d["dh"])

    def block(at):
        s = jnp.einsum("tkgd,skd->kgts", q[at], k) * F32(d["att"])
        s = jnp.where(pos[None, :] <= at[:, None], s, -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)

    return mm(_blocks(block, T, QUERY_BLOCK, pos).reshape(T, -1), p["o"])


def recurrence(x, delta, a, b, c, record_at, state_round=None):
    """The Mamba-2 recurrence over the whole sequence for some heads: x
    [T, h, P], delta [T, h], a [h], b, c [T, N]. Returns (y [T, h, P]
    without the skip, s [len(record_at), h, P, N] after each of the
    positions ``record_at``)."""
    def token(carry, t):
        s, kept = carry
        s = jnp.exp(delta[t] * a)[:, None, None] * s \
            + (delta[t][:, None] * x[t])[:, :, None] * b[t][None, None, :]
        if state_round is not None:
            s = state_round(s)
        kept = jnp.where((record_at == t)[:, None, None, None], s[None],
                         kept)
        return (s, kept), jnp.einsum("hpn,n->hp", s, c[t])

    h, P, N = x.shape[1], x.shape[2], b.shape[1]
    start = (jnp.zeros((h, P, N), F32),
             jnp.zeros((record_at.shape[0], h, P, N), F32))
    (_s, kept), y = jax.lax.scan(token, start, jnp.arange(x.shape[0]))
    return y, kept


def mamba2(p, u, d, record_at, mm=jnp.matmul, state_round=None):
    """The Mamba-2 mixer over the whole sequence ``u`` [T, D], a group of
    heads at a time. Returns (out [T, D], s [len(record_at), H, P, N]
    after each of the positions ``record_at``)."""
    T, H, P, n, kw = u.shape[0], d["Hm"], d["P"], d["n"], d["kw"]
    G = min(HEAD_GROUP, H)
    z = mm(u, p["in_z"])
    xp = jnp.pad(mm(u, p["in_xbc"]), ((kw - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][j] * xp[j:j + T] for j in range(kw)))
    x = xbc[:, :H * P].reshape(T, H, P)
    b, c = xbc[:, H * P:H * P + n], xbc[:, H * P + n:]
    delta = jax.nn.softplus(mm(u, p["in_dt"]) + p["dt_bias"])     # [T, H]
    a = -jnp.exp(p["a_log"])

    def group(_, part):
        xg, dg, ag = part
        return None, recurrence(xg, dg, ag, b, c, record_at, state_round)

    def groups(v, axis):
        return jnp.moveaxis(
            v.reshape(v.shape[:axis] + (H // G, G) + v.shape[axis + 1:]),
            axis, 0)

    _, (y, kept) = jax.lax.scan(
        group, None, (groups(x, 1), groups(delta, 1), groups(a, 0)))
    y = jnp.moveaxis(y, 0, 1).reshape(T, H, P) \
        + p["d_skip"][None, :, None] * x
    kept = jnp.moveaxis(kept, 0, 1).reshape(
        (record_at.shape[0], H, P, n))
    gated = y.reshape(T, H * P) * jax.nn.silu(z)
    return mm(rms_norm(gated, p["mix_norm"], d["eps"]), p["out_proj"]), kept


def route(p, x, d, mm=jnp.matmul):
    """(the router's logits over ALL the routed experts, its own top-k)."""
    logits = mm(x, p["router"].astype(F32))
    return logits, jax.lax.top_k(logits, d["k"])[1]


def routed_part(p, x, d, chosen=None, mm=jnp.matmul):
    """``sum_{i chosen and held} g_i E_i(x)``, ``g`` the softmax over the
    chosen logits: the held experts' part of the routed sum, one expert at
    a time (upcast as it is used). Returns (part, the logits, the router's
    own choice)."""
    logits, own = route(p, x, d, mm)
    use = own if chosen is None else chosen
    g = jax.nn.softmax(jnp.take_along_axis(logits, use, -1), -1)
    held = p["gate"].shape[0]
    # [T, held] weight of each held expert for each token (0: not chosen,
    # or chosen and held elsewhere: one_hot of an id outside is all zero)
    dense = jnp.sum(
        jax.nn.one_hot(use - d["first"], held, dtype=F32) * g[..., None], 1)

    def one(acc, ew):
        gate, up, down, col = ew
        y = swiglu(x, gate.astype(F32), up.astype(F32), down.astype(F32),
                   mm)
        return acc + col[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], dense.T))
    return routed, logits, own


@functools.partial(jax.jit, static_argnums=(2, 3, 6, 7))
def layer(p, x, dkey, kind, record_at, chosen=None, quant=None,
          state_round=None):
    """One block on float32 ``x``; ``p`` is upcast here (the experts one
    at a time). ``quant`` rounds every matrix product's operands,
    ``state_round`` the state after every token (the lower-precision
    controls). Returns (y, s or None, the router's logits, its own
    choice)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    mixer = {k: v.astype(F32) for k, v in p["mixer"].items()}
    u = rms_norm(x, p["in_norm"].astype(F32), d["eps"])
    if kind == "attention":
        out, kept = attention(mixer, u, d, mm), None
    else:
        out, kept = mamba2(mixer, u, d, record_at, mm, state_round)
    h = x + d["res"] * out
    nx = rms_norm(h, p["ff_norm"].astype(F32), d["eps"])
    routed, logits, own = routed_part(p["ffn"], nx, d, chosen, mm)
    return (h + d["res"] * (routed + shared_part(p["ffn"], nx, mm)), kept,
            logits, own)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def head(x, norm, embed, eps, scaling, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    if quant is not None:
        x = quant(x)
    # the tied table a block of rows at a time: upcast whole it would be
    # 0.8 GB beside the served model
    block = next(b for b in range(min(VOCAB_BLOCK, embed.shape[0]), 0, -1)
                 if embed.shape[0] % b == 0)

    def rows(w):
        w = w.astype(F32)
        return x @ (w if quant is None else quant(w)).T

    out = jax.lax.map(rows, embed.reshape(-1, block, embed.shape[1]))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1) / scaling


def forward(params, tokens, desc, chosen=None, logits_at=None, states_at=(),
            quant=None, state_round=None):
    """The full forward over one sequence ``tokens`` [T].

    ``chosen``: per layer, None or ``[T, k]`` expert ids to use in place
    of the router's own choice. ``logits_at``: the positions whose logits
    are returned (all when None). ``states_at``: positions after which
    every Mamba-2 layer's ``s`` is returned. ``quant``: a function that
    rounds the operands of every matrix product, ``state_round`` one that
    rounds ``s`` after every token (the controls one precision down; None
    is float32). Returns ``{"logits" [n, V], "biased" (the router's
    logits), "own": per layer, "states": [per Mamba-2 layer,
    [len(states_at), H, P, N]]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    record_at = jnp.asarray(list(states_at) or [0], jnp.int32)
    states, biased, own = [], [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32) * d["emb"]
        for i, (p, kind) in enumerate(zip(params["layers"],
                                          layer_kinds(desc))):
            x, kept, b, o = layer(
                p, x, dkey, kind, record_at,
                None if chosen is None else chosen[i], quant, state_round)
            if kept is not None:
                states.append(kept)
            biased.append(b)
            own.append(o)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        logits = head(x, params["final_norm"], params["embed"], d["eps"],
                      d["logit"], quant)
    return {"logits": logits, "biased": biased, "own": own,
            "states": states}
