"""The plain reference of a decoder-only model of gated delta-rule LINEAR
attention layers (Kimi Delta Attention, arXiv:2510.26692; the
flash-linear-attention ``KimiDeltaAttention`` module) with a few gated
grouped-query attention layers among them and routed experts in every
layer (HF ``solar_open2``): float32 ``jax.numpy`` at HIGHEST matmul
precision, no kernels, no cache, no chunks, no batching, the recurrence a
plain loop over ``t``.

``x`` is ``[tokens, hidden]``; ``u`` a mixer's normed input.

    block:   h = x + Mixer_i(RMSNorm_in(x));   y = h + FFN(RMSNorm_ff(h))
    FFN:     Shared(x) + scale * sum_{i in top-k and HELD} w_i E_i(x);
             w the sigmoid scores of the k chosen (by score + selection
             bias over ALL the routed experts), normalised over the k
    linear layer (H heads, dk = dv = head_dim):
      q, k, v  = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))
                 (causal, depthwise, ``short_conv_kernel_size`` taps)
      q <- q / sqrt(|q|^2 + 1e-6) * dk^-1/2,  k <- k / sqrt(|k|^2 + 1e-6)
      g_t      = -exp(A_log_h) * softplus((u Wfa) Wfb + dt_bias)   [H, dk]
      beta_t   = 2 sigmoid(u Wb)                                   [H]
      S'       = Diag(exp g_t) S_{t-1}          (S [dk, dv], S_0 = 0)
      w        = beta_t (v_t - S'^T k_t)
      S_t      = S' + k_t w^T;      o_t = S_t^T q_t
      out      = (RMSNorm_head(o_t) * sigmoid((u Wga) Wgb + b_g)) Wo
    attention layer (``gqa_layers``): q, k, v, gate = u Wq, u Wk, u Wv,
      u Wg; NO positional encoding; causal softmax at head_dim^-1/2, a
      group of query heads a key/value head;
      out = (attn * sigmoid(gate)) Wo
    logits = RMSNorm_final(y_L) @ W_head

Departures from the published forward: none in the mathematics (the public
kernels keep ``S`` in float32 too). What ``config.json`` does not carry
(the gates' rank, the form of the attention gate, the initialisers) is the
configuration's ``assumed``. The PARAMETERS' layout is the served
program's, so that both sides hold one copy (``weights_solar.tree``): every
matrix is ``[in, out]``, a linear layer's three projections are one ``q |
k | v`` matrix and its three convolutions one ``[taps, q | k | v]``
weight. The same HELD shard of the experts and slice of the vocabulary as
the served model: the rest is other chips'.

The parameter tree (any float dtype, upcast here a layer, and an expert,
at a time)::

    {"embed" [V, D], "head" [D, V], "final_norm" [D],
     "layers": [{"in_norm", "ff_norm",
                 "mixer": {"q", "k", "v", "gate", "o"}        # attention
                   or     {"qkv", "conv_w", "f_a", "f_b", "dt_bias",
                           "a_log", "beta", "g_a", "g_b", "g_bias",
                           "o_norm", "o"},
                 "ffn": {"router", "router_bias", "gate", "up", "down",
                         "shared_gate", "shared_up", "shared_down"}}]}
"""

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.sparse_latent_moe_decoder import (
    _blocks,
    rms_norm,
    routed_part,
    shared_part,
)

F32 = jnp.float32
QUERY_BLOCK = 512     # queries a block of attention
HEAD_GROUP = 16       # heads of a linear layer walked at a time
L2_EPS = 1e-6


def dims(desc):
    shard = desc.get("expert_shard")
    lin = desc["linear_attn_config"]
    return dict(
        D=desc["hidden_size"], H=desc["num_attention_heads"],
        Hkv=desc["num_key_value_heads"], dh=desc["head_dim"],
        Hl=lin["num_heads"], dl=lin["head_dim"],
        kw=lin["short_conv_kernel_size"],
        eps=float(desc["rms_norm_eps"]), k=desc["num_experts_per_tok"],
        scale=float(desc["routed_scaling_factor"]),
        norm_topk=bool(desc["norm_topk_prob"]),
        first=int(shard["first"]) if shard else 0,
        beta_scale=2.0 if desc["kda_allow_neg_eigval"] else 1.0)


def layer_kinds(desc):
    return ["gqa" if i in desc["gqa_layers"] else "linear"
            for i in range(desc["num_hidden_layers"])]


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def attention(p, u, d, mm=jnp.matmul):
    """Gated causal grouped-query attention over the whole sequence, no
    positional encoding, a block of queries at a time."""
    T = u.shape[0]
    g = d["H"] // d["Hkv"]
    pos = jnp.arange(T)
    q = mm(u, p["q"]).reshape(T, d["Hkv"], g, d["dh"])
    k = mm(u, p["k"]).reshape(T, d["Hkv"], d["dh"])
    v = mm(u, p["v"]).reshape(T, d["Hkv"], d["dh"])

    def block(at):
        s = jnp.einsum("tkgd,skd->kgts", q[at], k) / jnp.sqrt(F32(d["dh"]))
        s = jnp.where(pos[None, :] <= at[:, None], s, -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)

    out = _blocks(block, T, QUERY_BLOCK, pos).reshape(T, -1)
    return mm(out * jax.nn.sigmoid(mm(u, p["gate"])), p["o"])


def delta_rule(q, k, v, g, beta, record_at, state_round=None):
    """The recurrence over the whole sequence for some heads: q, k, g
    [T, h, dk] (q and k normalised), v [T, h, dv], beta [T, h]. Returns
    (o [T, h, dv], S [len(record_at), h, dk, dv] after each of the
    positions ``record_at``)."""
    def token(carry, t):
        s, kept = carry
        sp = jnp.exp(g[t])[:, :, None] * s
        w = beta[t][:, None] * (v[t] - jnp.einsum("hkv,hk->hv", sp, k[t]))
        s = sp + k[t][:, :, None] * w[:, None, :]
        if state_round is not None:
            s = state_round(s)
        kept = jnp.where((record_at == t)[:, None, None, None], s[None],
                         kept)
        return (s, kept), jnp.einsum("hkv,hk->hv", s, q[t])

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    start = (jnp.zeros((h, dk, dv), F32),
             jnp.zeros((record_at.shape[0], h, dk, dv), F32))
    (_s, kept), o = jax.lax.scan(token, start, jnp.arange(q.shape[0]))
    return o, kept


def linear_attention(p, u, d, record_at, mm=jnp.matmul, state_round=None):
    """The delta-rule mixer over the whole sequence ``u`` [T, D], a group
    of heads at a time. Returns (out [T, D], S [len(record_at), H, dk, dv]
    after each of the positions ``record_at``)."""
    T, H, dl, kw = u.shape[0], d["Hl"], d["dl"], d["kw"]
    G = min(HEAD_GROUP, H)
    n, gw = H // G, min(HEAD_GROUP, H) * dl
    decay = mm(mm(u, p["f_a"]), p["f_b"]) + p["dt_bias"]          # [T, H dl]
    g = -(jax.nn.softplus(decay).reshape(T, H, dl)
          * jnp.exp(p["a_log"])[None, :, None])
    beta = d["beta_scale"] * jax.nn.sigmoid(mm(u, p["beta"]))     # [T, H]
    gate = mm(mm(u, p["g_a"]), p["g_b"]) + p["g_bias"]

    def part(j):
        """(the projections, the convolutions) of q, k or v by group."""
        lo = j * H * dl
        return (p["qkv"][:, lo:lo + H * dl].reshape(-1, n, gw),
                p["conv_w"][:, lo:lo + H * dl].reshape(kw, n, gw))

    def conv(x, w):
        xp = jnp.pad(x, ((kw - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[j] * xp[j:j + T] for j in range(kw)))

    def group(_, ws):
        (wq, cq), (wk, ck), (wv, cv), gg, bb = ws
        q, k, v = [conv(mm(u, w), c).reshape(T, G, dl)
                   for w, c in ((wq, cq), (wk, ck), (wv, cv))]
        o, kept = delta_rule(l2_normalise(q) * dl ** -0.5, l2_normalise(k),
                             v, gg, bb, record_at, state_round)
        return None, (rms_norm(o, p["o_norm"], d["eps"]), kept)

    by_group = [tuple(jnp.moveaxis(a, 1, 0) for a in part(j))
                for j in range(3)]
    _, (o, kept) = jax.lax.scan(group, None, (
        by_group[0], by_group[1], by_group[2],
        jnp.moveaxis(g.reshape(T, n, G, dl), 1, 0),
        jnp.moveaxis(beta.reshape(T, n, G), 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * dl)          # [n, T, G, dl]
    kept = jnp.moveaxis(kept, 0, 1).reshape(
        (record_at.shape[0], H) + kept.shape[-2:])
    return mm(o * jax.nn.sigmoid(gate), p["o"]), kept


@functools.partial(jax.jit, static_argnums=(2, 3, 6, 7))
def layer(p, x, dkey, kind, record_at, chosen=None, quant=None,
          state_round=None):
    """One block on float32 ``x``; ``p`` is upcast here (the experts one
    at a time). ``quant`` rounds every matrix product's operands,
    ``state_round`` the state after every token (the lower-precision
    controls). Returns (y, S or None, s + b, the router's own choice)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    mixer = {k: v.astype(F32) for k, v in p["mixer"].items()}
    u = rms_norm(x, p["in_norm"].astype(F32), d["eps"])
    if kind == "gqa":
        out, kept = attention(mixer, u, d, mm), None
    else:
        out, kept = linear_attention(mixer, u, d, record_at, mm,
                                     state_round)
    h = x + out
    nx = rms_norm(h, p["ff_norm"].astype(F32), d["eps"])
    routed, biased, own = routed_part(p["ffn"], nx, d, chosen, mm)
    return h + routed + shared_part(p["ffn"], nx, mm), kept, biased, own


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, w, eps, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    w = w.astype(F32)
    return x @ w if quant is None else quant(x) @ quant(w)


def forward(params, tokens, desc, chosen=None, logits_at=None, states_at=(),
            quant=None, state_round=None):
    """The full forward over one sequence ``tokens`` [T].

    ``chosen``: per layer, None or ``[T, k]`` expert ids to use in place
    of the router's own choice. ``logits_at``: the positions whose logits
    are returned (all when None). ``states_at``: positions after which
    every linear layer's ``S`` is returned. ``quant``: a function that
    rounds the operands of every matrix product, ``state_round`` one that
    rounds ``S`` after every token (the controls one precision down; None
    is float32). Returns ``{"logits" [n, V], "biased", "own": per layer,
    "states": [per linear layer, [len(states_at), H, dk, dv]]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    record_at = jnp.asarray(list(states_at) or [0], jnp.int32)
    states, biased, own = [], [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
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
        logits = head(x, params["final_norm"], params["head"], d["eps"],
                      quant)
    return {"logits": logits, "biased": biased, "own": own,
            "states": states}
