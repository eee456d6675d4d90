"""The plain reference of a hybrid state-space / attention decoder-only
model (HF ``JambaConfig`` / ``modeling_jamba``'s forward with
``num_experts`` 1): float32 ``jax.numpy`` at HIGHEST matmul precision, no
kernels, no cache, no batching, the recurrence a plain loop over ``t``.

``x`` is ``[tokens, hidden]`` (``D``). Layer ``i`` is attention when ``i %
attn_layer_period == attn_layer_offset``, else the state-space mixer
(HF's ``layers_block_type``; the catalog does not give the order of the
layer types, so this reading is listed under the configuration's
``assumed``):

    block:   h = x + Mixer_i(RMSNorm_in(x));   y = h + MLP(RMSNorm_ff(h))
    MLP:     down(silu(gate(u)) * up(u)), no bias
    Mamba (d = mamba_expand * D, n = mamba_d_state, kw = mamba_d_conv,
           r = mamba_dt_rank):
      [x | z]      = in_proj(u)                          # D -> 2 d
      x_t          = silu(b_conv + sum_j W_conv[j] * x_{t-(kw-1)+j})
      [dt|B_t|C_t] = x_proj(x_t)                         # d -> r + 2 n
      dt, B_t, C_t = RMSNorm_dt(dt), RMSNorm_b(B_t), RMSNorm_c(C_t)
      Delta_t      = softplus(dt_proj(dt) + b_dt)        # r -> d, bias
      A            = -exp(A_log)                         # [d, n]
      s_t          = exp(Delta_t (x) A) * s_{t-1} + (Delta_t * x_t) (x) B_t
      y_t          = s_t . C_t + D_skip * x_t            # s_0 = 0
      out          = out_proj(y_t * silu(z_t))           # d -> D
    Attention: ``num_attention_heads`` query heads over
      ``num_key_value_heads`` key/value heads of width D / heads, q/k/v/o
      without bias, NO positional encoding of any kind, causal softmax at
      scale head^-0.5
    logits = RMSNorm_final(y_L) @ Embedding^T

Departures from the published forward: none in the mathematics. The
PARAMETERS' layout is the served program's, so that both sides hold one
copy (``weights_jamba.tree`` lays the same arrays out): every matrix is
``[in, out]``, ``a_log`` is ``[n, d]`` and ``conv_w`` ``[kw, d]`` (HF
stores ``[out, in]``, ``[d, n]`` and ``[d, 1, kw]``); the state is
returned ``[d, n]`` as the equations have it. Head width D / heads, the
seeded ranges of ``a_log`` and ``dt_bias`` and the ignored EOS are the
configuration's ``assumed``.

The parameter tree (any float dtype, upcast here a layer at a time so
that the published widths fit one chip beside the served copy)::

    {"embed" [V, D], "final_norm" [D],
     "layers": [{"in_norm", "ff_norm", "ffn": {"gate", "up", "down"},
                 "mixer": {"q", "k", "v", "o"}                # attention
                   or     {"in_proj", "conv_w", "conv_b", "x_proj",
                           "dt_norm", "b_norm", "c_norm", "dt_proj",
                           "dt_bias", "a_log", "d_skip", "out_proj"}}]}
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(desc):
    D, H = desc["hidden_size"], desc["num_attention_heads"]
    return dict(D=D, H=H, Hkv=desc["num_key_value_heads"], dh=D // H,
                d=desc["mamba_expand"] * D, n=desc["mamba_d_state"],
                kw=desc["mamba_d_conv"], r=desc["mamba_dt_rank"],
                eps=float(desc["rms_norm_eps"]))


def layer_kinds(desc):
    return ["attention" if i % desc["attn_layer_period"]
            == desc["attn_layer_offset"] else "mamba"
            for i in range(desc["num_hidden_layers"])]


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def swiglu(x, gate, up, down, mm=jnp.matmul):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def attention(p, x, d, mm=jnp.matmul):
    """Causal grouped-query attention over the whole sequence, no
    positional encoding."""
    T = x.shape[0]
    g = d["H"] // d["Hkv"]
    q = mm(x, p["q"]).reshape(T, d["Hkv"], g, d["dh"])
    k = mm(x, p["k"]).reshape(T, d["Hkv"], d["dh"])
    v = mm(x, p["v"]).reshape(T, d["Hkv"], d["dh"])
    s = jnp.einsum("tkgd,skd->kgts", q, k) / jnp.sqrt(F32(d["dh"]))
    pos = jnp.arange(T)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
    return mm(out.reshape(T, -1), p["o"])


def mamba(p, u, d, record_at, mm=jnp.matmul, state_round=None):
    """The state-space mixer over the whole sequence ``u`` [T, D]. Returns
    (out [T, D], the state ``s`` [len(record_at), d, n] after each of the
    positions ``record_at``, the convolution's inputs [len(record_at),
    kw - 1, d] ending at each of them). ``state_round`` rounds ``s`` after
    every token (the control that keeps it in a lower precision)."""
    T, n, r, kw = u.shape[0], d["n"], d["r"], d["kw"]
    xz = mm(u, p["in_proj"])
    x, z = xz[:, :d["d"]], xz[:, d["d"]:]
    xp = jnp.pad(x, ((kw - 1, 0), (0, 0)))
    xc = jax.nn.silu(p["conv_b"] + sum(p["conv_w"][j] * xp[j:j + T]
                                       for j in range(kw)))
    dbc = mm(xc, p["x_proj"])
    dt = rms_norm(dbc[:, :r], p["dt_norm"], d["eps"])
    b = rms_norm(dbc[:, r:r + n], p["b_norm"], d["eps"])
    c = rms_norm(dbc[:, r + n:], p["c_norm"], d["eps"])
    delta = jax.nn.softplus(mm(dt, p["dt_proj"]) + p["dt_bias"])   # [T, d]
    a = -jnp.exp(p["a_log"]).T                                     # [d, n]
    record_at = jnp.asarray(record_at)

    def token(carry, t):
        s, kept = carry
        s = (jnp.exp(delta[t][:, None] * a) * s
             + (delta[t] * xc[t])[:, None] * b[t][None, :])
        if state_round is not None:
            s = state_round(s)
        y = s @ c[t] + p["d_skip"] * xc[t]
        kept = jnp.where((record_at == t)[:, None, None], s[None], kept)
        return (s, kept), y

    start = (jnp.zeros((d["d"], n), F32),
             jnp.zeros((record_at.shape[0], d["d"], n), F32))
    (_s, kept), y = jax.lax.scan(token, start, jnp.arange(T))
    windows = jnp.stack([xp[record_at + 1 + j] for j in range(kw - 1)], 1)
    return mm(y * jax.nn.silu(z), p["out_proj"]), kept, windows


@functools.partial(jax.jit, static_argnums=(2, 3, 5, 6))
def layer(p, x, dkey, kind, record_at, quant=None, state_round=None):
    """One block on float32 ``x``; ``p`` is upcast here. ``dkey``:
    ``dims`` as sorted items. ``quant`` rounds every matrix product's
    operands (the lower-precision control). Returns (y, state or None,
    windows or None)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    up = {k: (v.astype(F32) if not isinstance(v, dict) else
              {kk: vv.astype(F32) for kk, vv in v.items()})
          for k, v in p.items()}
    nx = rms_norm(x, up["in_norm"], d["eps"])
    if kind == "attention":
        out, kept, windows = attention(up["mixer"], nx, d, mm), None, None
    else:
        out, kept, windows = mamba(up["mixer"], nx, d, record_at, mm,
                                   state_round)
    h = x + out
    f = up["ffn"]
    y = h + swiglu(rms_norm(h, up["ff_norm"], d["eps"]), f["gate"],
                   f["up"], f["down"], mm)
    return y, kept, windows


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, table, eps, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    w = table.astype(F32).T
    return x @ w if quant is None else quant(x) @ quant(w)


def forward(params, tokens, desc, logits_at=None, states_at=(),
            quant=None, state_round=None):
    """The full forward over one sequence ``tokens`` [T].

    ``logits_at``: the positions whose logits are returned (all when
    None). ``states_at``: positions after which every state-space layer's
    ``s`` and convolution window are returned. ``quant``: a function that
    rounds the operands of every matrix product, ``state_round`` one that
    rounds ``s`` after every token (the controls one precision down; None
    is float32). Returns ``{"logits" [n, V], "states": [per state-space
    layer, [len(states_at), d, n]], "windows": [per state-space layer,
    [len(states_at), kw - 1, d]]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    record_at = jnp.asarray(list(states_at) or [0], jnp.int32)
    states, windows = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for p, kind in zip(params["layers"], layer_kinds(desc)):
            x, kept, win = layer(p, x, dkey, kind, record_at, quant,
                                 state_round)
            if kept is not None:
                states.append(kept)
                windows.append(win)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        logits = head(x, params["final_norm"], params["embed"], d["eps"],
                      quant)
    return {"logits": logits, "states": states, "windows": windows}
