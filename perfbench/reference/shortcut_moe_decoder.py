"""The plain reference of a decoder-only model whose layer holds two
latent-attention (MLA) blocks and two dense feed-forwards with the
routed-expert block on a shortcut across them, 256 of the router's outputs
zero-compute (identity) experts (HF ``longcat_flash``,
``LongcatFlashDecoderLayer``): float32 ``jax.numpy`` at HIGHEST matmul
precision, no kernels, no cache, no batching. Attention is computed in the
EXPANDED form only, a group of heads and a block of queries at a time, so
the program's absorbed-form decode is checked against independent
arithmetic; the experts are a plain loop over the held ones with a dense
mask; every matrix is upcast as it is used (a dense feed-forward a slice
of its columns at a time), so the published widths fit beside the served
weights.

``x`` is ``[tokens, hidden]``. One layer::

    h0 = x  + MLA_0(RMSNorm(x));   u0 = RMSNorm(h0)
    m  = MoE(u0)                                  # added at the END
    h1 = h0 + FFN_0(u0)
    h2 = h1 + MLA_1(RMSNorm(h1));  u1 = RMSNorm(h2)
    y  = h2 + FFN_1(u1) + m

``MLA``: ``cq = RMSNorm(n Wqa)``, ``q = (cq Wqb) * sqrt(D / rq)`` (where
``mla_scale_q_lora``), ``[ckv | kr] = n Wkva``, ``c = RMSNorm(ckv) * sqrt(D
/ C)`` (where ``mla_scale_kv_lora``; ``kr`` is not scaled), RoPE over
adjacent pairs on ``q``'s rotary part and on ``kr``, ``[k_nope | v] = c
Wkvb`` a head, scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(dn +
dr)``, causal softmax, ``Wo``. ``MoE``: ``p = softmax(u Wr)`` over the
``E_all + Z`` outputs, the ``moe_topk`` largest of ``p + b`` chosen, ``w =
routed_scaling_factor * p`` of the chosen (not renormalised); ``sum_{i <
E_all, held} w_i Expert_i(u) + (sum_{i >= E_all} w_i) u``. After the last
layer RMSNorm, then the untied head.

With ``expert_shard`` the tree holds the HELD experts (``first .. first +
E - 1`` of ``E_all``) and a chosen expert held elsewhere adds nothing, as
in the program; the identities are all computed here.

Controls (``forward``'s keywords; each must fail the check):
``quant`` rounds every product's operands (one precision down);
``identities=False`` leaves the identities' term out; ``sequential=True``
feeds the expert block from ``u1`` (a plain sequential layer, no
shortcut).

Departures from the published model (the configuration's ``assumed``
lists them): the audio and vision encoders and the codec decoder are not
loaded; the text decoder is fed token ids.

The parameter tree (``weights_longcat.py`` makes it; any float dtype)::

    {"embed" [V, D], "head" [D, V], "final_norm" [D],
     "layers": [{"sub": [{"attn_norm", "q_a", "q_norm", "q_b", "kv_a",
                          "kv_norm", "kv_b", "o", "ffn_norm", "ffn_gate",
                          "ffn_up", "ffn_down"}] * 2,
                 "moe": {"router" [D, E_all + Z], "router_bias",
                         "gate" [E, D, Fe], "up", "down"}}]}
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512     # queries a block of attention
HEAD_GROUP = 8        # heads expanded at a time
TOKEN_BLOCK = 2048    # tokens a block of a feed-forward
COLUMN_BLOCK = 2048   # columns of a dense feed-forward upcast at a time


def dims(desc):
    shard = desc.get("expert_shard")
    D = desc["hidden_size"]
    return dict(
        D=D, H=desc["num_attention_heads"],
        dn=desc["qk_nope_head_dim"], dr=desc["qk_rope_head_dim"],
        dv=desc["v_head_dim"], C=desc["kv_lora_rank"],
        eps=float(desc["rms_norm_eps"]), theta=float(desc["rope_theta"]),
        k=desc["moe_topk"], Z=desc["zero_expert_num"],
        scale=float(desc["routed_scaling_factor"]),
        q_scale=(D / float(desc["q_lora_rank"])) ** 0.5
        if desc.get("mla_scale_q_lora") else 1.0,
        kv_scale=(D / float(desc["kv_lora_rank"])) ** 0.5
        if desc.get("mla_scale_kv_lora") else 1.0,
        first=int(shard["first"]) if shard else 0)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """``x`` [T, ..., d], rotary pairs (2i, 2i + 1), kept in place."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    re, im = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([re * jnp.cos(ang) - im * jnp.sin(ang),
                     re * jnp.sin(ang) + im * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def _blocks(fn, n, block, *arrays):
    """``fn`` over ``block`` rows of ``arrays`` at a time ([n, ...] each,
    padded to whole blocks), the results laid back to [n, ...]."""
    block = min(block, n)
    pad = -n % block
    parts = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (-1, block) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda p: fn(*p), tuple(parts))
    return out.reshape((-1,) + out.shape[2:])[:n]


def swiglu(x, gate, up, down, mm=jnp.matmul):
    """``(silu(x Wg) * (x Wu)) Wd`` on float32 matrices, a block of tokens
    at a time."""
    return _blocks(
        lambda part: mm(jax.nn.silu(mm(part, gate)) * mm(part, up), down),
        x.shape[0], TOKEN_BLOCK, x)


def dense_ffn(p, x, mm=jnp.matmul):
    """A sub-block's SwiGLU of width ``ffn_hidden_size``, its matrices
    upcast ``COLUMN_BLOCK`` columns at a time (the sum over the hidden
    columns is the sum of the slices' parts)."""
    F = p["ffn_gate"].shape[1]
    n = max(1, F // COLUMN_BLOCK)
    while F % n:
        n -= 1
    gate = jnp.moveaxis(p["ffn_gate"].reshape(-1, n, F // n), 1, 0)
    up = jnp.moveaxis(p["ffn_up"].reshape(-1, n, F // n), 1, 0)
    down = p["ffn_down"].reshape(n, F // n, -1)

    def one(acc, w):
        g, u, dn = (a.astype(F32) for a in w)
        return acc + swiglu(x, g, u, dn, mm), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (gate, up, down))
    return out


def attention(p, n, d, mm=jnp.matmul):
    """Causal MLA of one sub-block over the whole sequence on its normed
    rows ``n``, expanded form, a group of heads at a time; ``p`` holds the
    sub-block's matrices as they are stored."""
    T = n.shape[0]
    pos = jnp.arange(T)
    H, dn, dr, dv = d["H"], d["dn"], d["dr"], d["dv"]
    G = min(HEAD_GROUP, H)
    cq = rms_norm(mm(n, p["q_a"].astype(F32)), p["q_norm"].astype(F32),
                  d["eps"])
    kva = mm(n, p["kv_a"].astype(F32))
    c = rms_norm(kva[:, :d["C"]], p["kv_norm"].astype(F32),
                 d["eps"]) * d["kv_scale"]
    k_rope = rope(kva[:, d["C"]:], pos, d["theta"])          # [T, dr]
    q_b = p["q_b"].reshape(-1, H // G, G * (dn + dr))
    kv_b = p["kv_b"].reshape(-1, H // G, G * (dn + dv))
    o = p["o"].reshape(H // G, G * dv, -1)

    def group(acc, w):
        wq, wkv, wo = (a.astype(F32) for a in w)
        q = (mm(cq, wq) * d["q_scale"]).reshape(T, G, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, d["theta"])
        kv = mm(c, wkv).reshape(T, G, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def block(at):
            if mm is jnp.matmul:
                s = (jnp.einsum("thn,shn->hts", q_nope[at], k_nope)
                     + jnp.einsum("thr,sr->hts", q_rope[at], k_rope))
            else:
                # the control's rounded operands: one product a head
                qq = jnp.concatenate([q_nope[at], q_rope[at]], -1)
                kk = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_rope[:, None], (T, G, dr))],
                    -1)
                s = jnp.stack([mm(qq[:, h], kk[:, h].T) for h in range(G)])
            s = s / jnp.sqrt(F32(dn + dr))
            s = jnp.where((pos[None, :] <= at[:, None])[None], s, -jnp.inf)
            pr = jax.nn.softmax(s, -1)
            if mm is jnp.matmul:
                return jnp.einsum("hts,shv->thv", pr, v)
            return jnp.stack([mm(pr[h], v[:, h]) for h in range(G)], 1)

        out = _blocks(block, T, QUERY_BLOCK, pos)            # [T, G, dv]
        return acc + mm(out.reshape(T, -1), wo), None

    out, _ = jax.lax.scan(
        group, jnp.zeros((T, o.shape[-1]), F32),
        (jnp.moveaxis(q_b, 1, 0), jnp.moveaxis(kv_b, 1, 0), o))
    return out


def route(p, u, d, mm=jnp.matmul):
    """(the router's ``p`` [T, E_all + Z], ``p + b``, its own choice
    [T, k])."""
    prob = jax.nn.softmax(mm(u, p["router"].astype(F32)), -1)
    biased = prob + p["router_bias"].astype(F32)
    return prob, biased, jax.lax.top_k(biased, d["k"])[1]


def moe(p, u, d, chosen=None, mm=jnp.matmul, identities=True):
    """``sum_{i chosen, real and held} w_i Expert_i(u) + (sum_{i chosen,
    an identity} w_i) u`` with ``w = scale * p`` over ``chosen`` (the
    router's own choice when None), one held expert at a time (upcast as
    it is used). Returns (out, ``p + b``, the router's own choice)."""
    prob, biased, own = route(p, u, d, mm)
    use = own if chosen is None else chosen
    w = d["scale"] * jnp.take_along_axis(prob, use, -1)
    real = prob.shape[1] - d["Z"]
    held = p["gate"].shape[0]
    # [T, held] weight of each held expert for each token (0: not chosen,
    # chosen and held elsewhere, or an identity: one_hot of an id outside
    # is all zero)
    dense = jnp.sum(
        jax.nn.one_hot(use - d["first"], held, dtype=F32) * w[..., None], 1)

    def one(acc, ew):
        gate, up, down, col = ew
        y = swiglu(u, gate.astype(F32), up.astype(F32), down.astype(F32),
                   mm)
        return acc + col[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u), (p["gate"], p["up"], p["down"], dense.T))
    if identities:
        out = out + jnp.sum(jnp.where(use >= real, w, 0.0), -1)[:, None] * u
    return out, biased, own


@functools.partial(jax.jit, static_argnums=(2, 4, 5, 6))
def layer(p, x, dkey, chosen=None, quant=None, identities=True,
          sequential=False):
    """One layer on float32 ``x``. ``dkey``: ``dims`` as sorted items.
    Returns (y, ``p + b``, the router's own choice, the expert block's
    input)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    h, us = x, []
    for sub in p["sub"]:
        n = rms_norm(h, sub["attn_norm"].astype(F32), d["eps"])
        h = h + attention(sub, n, d, mm)
        us.append(rms_norm(h, sub["ffn_norm"].astype(F32), d["eps"]))
        h = h + dense_ffn(sub, us[-1], mm)
    fed = us[1] if sequential else us[0]
    m, biased, own = moe(p["moe"], fed, d, chosen, mm, identities)
    return h + m, biased, own, fed


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, w, eps, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    w = w.astype(F32)
    return x @ w if quant is None else quant(x) @ quant(w)


def forward(params, tokens, desc, chosen=None, logits_at=None, quant=None,
            identities=True, sequential=False):
    """The full forward over one sequence ``tokens`` [T].

    ``chosen``: per layer, None or ``[T, k]`` router outputs to use in
    place of the router's own choice (the program's, for the comparison
    under its choice). ``logits_at``: the positions whose logits are
    returned (all when None). ``quant`` / ``identities`` / ``sequential``:
    the controls (module docstring). Returns ``{"logits" [n, V],
    "biased": per layer [T, E_all + Z], "own": per layer [T, k],
    "fed": per layer, the expert block's input [T, D]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        biased, own, fed = [], [], []
        for i, p in enumerate(params["layers"]):
            x, b, o, u = layer(p, x, dkey,
                               None if chosen is None else chosen[i], quant,
                               identities, sequential)
            biased.append(b)
            own.append(o)
            fed.append(u)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        logits = head(x, params["final_norm"], params["head"], d["eps"],
                      quant)
    return {"logits": logits, "biased": biased, "own": own, "fed": fed}
