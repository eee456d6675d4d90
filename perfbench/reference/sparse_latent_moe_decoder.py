"""The plain reference of a decoder-only model with latent (MLA) attention
under LEARNED SPARSE attention and routed experts (HF ``glm_moe_dsa``;
the indexer as DeepSeek-V3.2-Exp ``inference/model.py`` has it): float32
``jax.numpy`` at HIGHEST matmul precision, no kernels, no cache, no
batching, one sequence. ``u`` is a block's input after its RMSNorm, ``t``
a query position, ``s <= t`` an earlier one.

    MLA      cq = RMSNorm(u Wqa); q = cq Wqb -> H heads of [nope | rope],
             RoPE over INTERLEAVED pairs on the rope part;
             [ckv | kr] = u Wkva; c = RMSNorm(ckv), kr = RoPE(kr);
             k_{s,h} = [c_s Wkb^K_h | kr_s], v_{s,h} = c_s Wkb^V_h
    indexer  (a layer whose ``indexer_types`` is ``full``)
             qI_{t,j} = cq_t WIq_j; kI_s = LayerNorm(u_s WIk); RoPE on
             the first ``qk_rope_head_dim`` columns of both;
             w_{t,j} = (u_t WIw)_j * J^-1/2 * dI^-1/2;
             I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s);
             S_t = the ``index_topk`` positions s <= t of largest I_{t,s}
             (all of them while t < index_topk; ties to the lower s)
    shared   a ``shared`` layer uses S_t of the nearest ``full`` layer
             before it
    attend   o_{t,h} = sum_{s in S_t} softmax_s(q_{t,h} . k_{s,h} / sqrt(dq))
             v_{s,h}, then Wo
    FFN      SwiGLU in the leading dense layers; else Shared(x) + scale *
             sum_{i in top-k and HELD} g_i E_i(x), g the sigmoid scores of
             the k chosen (by score + bias over ALL the router's outputs)
             normalised over the k: the experts ``expert_shard.first`` and
             on that ``n_routed_experts`` counts are held, the others' part
             is left out, as in the program

Attention is computed in the EXPANDED form only, a group of heads and a
block of queries at a time so that 16 k positions fit beside the served
model; the selection is a ``[T, T]`` int8 mask made a block of queries at
a time and handed from a ``full`` layer to the ``shared`` layers behind it.

Departures from the published forward (the configuration's ``assumed``
lists them): the published code rotates ``qI`` / ``kI`` by a Hadamard
matrix and quantises them to float8 before their product (an orthogonal
map, which leaves every product as it is, and a precision: float32 here);
the multi-token-prediction layer is not loaded.

The parameter tree (``weights_glm52.py`` lays it out over the program's
arrays; any float dtype, upcast here a layer, and in it an expert, at a
time)::

    {"embed" [V, D], "head" [D, V], "final_norm" [D],
     "layers": [{"attn_norm", "q_a", "q_norm", "q_b", "kv_a", "kv_norm",
                 "kv_b", "o", "ffn_norm",
                 "indexer": {"q", "k", "k_norm", "k_shift", "w"} or absent,
                 "ffn": {"gate", "up", "down"}            # a dense layer
                   or  {"router" [D, E_all], "router_bias", "gate"
                        [E_held, D, F], "up", "down", "shared_gate",
                        "shared_up", "shared_down"}}]}
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512     # queries a block of attention and of the selection
HEAD_GROUP = 8        # heads expanded at a time
TOKEN_BLOCK = 2048    # tokens a block of a feed-forward


def dims(desc):
    shard = desc.get("expert_shard")
    return dict(
        D=desc["hidden_size"], H=desc["num_attention_heads"],
        dn=desc["qk_nope_head_dim"], dr=desc["qk_rope_head_dim"],
        dv=desc["v_head_dim"], C=desc["kv_lora_rank"],
        eps=float(desc["rms_norm_eps"]),
        theta=float(desc.get("rope_theta")
                    or desc["rope_parameters"]["rope_theta"]),
        k=desc["num_experts_per_tok"],
        scale=float(desc["routed_scaling_factor"]),
        norm_topk=bool(desc["norm_topk_prob"]),
        first=int(shard["first"]) if shard else 0,
        topk=int(desc["index_topk"]), J=desc["index_n_heads"],
        dI=desc["index_head_dim"])


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layer_norm(x, scale, shift, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + shift


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
    pad = -n % block
    parts = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (-1, block) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda p: fn(*p), tuple(parts))
    return out.reshape((-1,) + out.shape[2:])[:n]


def swiglu(x, gate, up, down, mm=jnp.matmul):
    return _blocks(
        lambda part: mm(jax.nn.silu(mm(part, gate)) * mm(part, up), down),
        x.shape[0], TOKEN_BLOCK, x)


def index_scores(p, cq, u, d, mm=jnp.matmul):
    """``I`` [T, T] would not fit: returns a function of a block of query
    positions that gives their rows [Q, T], ``-inf`` above the diagonal."""
    T = u.shape[0]
    pos = jnp.arange(T)
    qI = mm(cq, p["q"]).reshape(T, d["J"], d["dI"])
    qI = jnp.concatenate([rope(qI[..., :d["dr"]], pos, d["theta"]),
                          qI[..., d["dr"]:]], -1)
    kI = layer_norm(mm(u, p["k"]), p["k_norm"], p["k_shift"])
    kI = jnp.concatenate([rope(kI[:, :d["dr"]], pos, d["theta"]),
                          kI[:, d["dr"]:]], -1)
    w = mm(u, p["w"]) * (d["J"] ** -0.5 * d["dI"] ** -0.5)

    def rows(at):
        if mm is jnp.matmul:
            dots = jnp.einsum("qjd,sd->qjs", qI[at], kI)
        else:
            dots = mm(qI[at].reshape(-1, d["dI"]), kI.T).reshape(
                len(at), d["J"], T)
        score = jnp.sum(jax.nn.relu(dots) * w[at][:, :, None], axis=1)
        return jnp.where(pos[None, :] <= at[:, None], score, -jnp.inf)

    return rows


def top_k_rows(scores, k):
    """[Q, T] bool: each row's ``k`` largest finite scores (all of them
    where it has fewer), ties to the lower position."""
    k = min(k, scores.shape[-1])
    values, idx = jax.lax.top_k(scores, k)
    hit = jnp.zeros(scores.shape, bool)
    hit = hit.at[jnp.arange(scores.shape[0])[:, None], idx].set(
        values > -jnp.inf)
    return hit


def selection(p, cq, u, d, index_at, mm=jnp.matmul):
    """(mask [T, T] int8 of every row's own choice, the scores of the
    rows ``index_at`` [n, T])."""
    T = u.shape[0]
    rows = index_scores(p, cq, u, d, mm)
    mask = _blocks(
        lambda at: top_k_rows(rows(at), d["topk"]).astype(jnp.int8),
        T, QUERY_BLOCK, jnp.arange(T))
    return mask, rows(index_at)


def attention(p, u, cq, d, mask, mm=jnp.matmul):
    """MLA over the positions ``mask`` [T, T] gives each query (None:
    every earlier one), expanded form, a group of heads at a time."""
    T = u.shape[0]
    pos = jnp.arange(T)
    H, dn, dr, dv = d["H"], d["dn"], d["dr"], d["dv"]
    G = min(HEAD_GROUP, H)
    kva = mm(u, p["kv_a"])
    ckv = rms_norm(kva[:, :d["C"]], p["kv_norm"], d["eps"])
    k_rope = rope(kva[:, d["C"]:], pos, d["theta"])          # [T, dr]
    q_b = p["q_b"].reshape(-1, H // G, G * (dn + dr))
    kv_b = p["kv_b"].reshape(-1, H // G, G * (dn + dv))
    o = p["o"].reshape(H // G, G * dv, -1)

    def group(acc, w):
        wq, wkv, wo = w
        q = mm(cq, wq).reshape(T, G, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, d["theta"])
        kv = mm(ckv, wkv).reshape(T, G, dn + dv)
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
            see = pos[None, :] <= at[:, None]
            if mask is not None:
                see = see & (mask[at] != 0)
            s = jnp.where(see[None], s, -jnp.inf)
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


def route(p, x, d, mm=jnp.matmul):
    """(the router's ``s`` [T, E_all], ``s + b``, its own choice [T, k])."""
    s = jax.nn.sigmoid(mm(x, p["router"].astype(F32)))
    biased = s + p["router_bias"].astype(F32)
    return s, biased, jax.lax.top_k(biased, d["k"])[1]


def routed_part(p, x, d, chosen=None, mm=jnp.matmul):
    """``scale * sum_{i chosen and held} g_i E_i(x)``: the held experts'
    part of the routed sum, one expert at a time (upcast as it is used).
    Returns (part, s + b, the router's own choice)."""
    s, biased, own = route(p, x, d, mm)
    use = own if chosen is None else chosen
    g = jnp.take_along_axis(s, use, -1)
    if d["norm_topk"]:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
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
    return d["scale"] * routed, biased, own


def shared_part(p, x, mm=jnp.matmul):
    return swiglu(x, p["shared_gate"].astype(F32),
                  p["shared_up"].astype(F32),
                  p["shared_down"].astype(F32), mm)


@functools.partial(jax.jit, static_argnums=(2, 5, 6))
def layer(p, x, dkey, chosen=None, mask=None, quant=None, select=True,
          index_at=None, forced=None):
    """One block on float32 ``x``; ``p`` is upcast here (the experts one
    at a time). ``mask``: the choice of the ``full`` layer before (None
    for a ``full`` layer, which makes its own). ``forced`` = (rows [n],
    their choice [n, T] bool) replaces the own choice of those rows.
    Returns (y, mask, s + b, the router's own choice, the index scores of
    the rows ``index_at``)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    ffn, idx = p["ffn"], p.get("indexer")
    p = {k: v.astype(F32) for k, v in p.items()
         if k not in ("ffn", "indexer")}
    u = rms_norm(x, p["attn_norm"], d["eps"])
    cq = rms_norm(mm(u, p["q_a"]), p["q_norm"], d["eps"])
    scores = None
    if idx is not None and select:
        idx = {k: v.astype(F32) for k, v in idx.items()}
        mask, scores = selection(idx, cq, u, d, index_at, mm)
        if forced is not None:
            mask = mask.at[forced[0]].set(forced[1].astype(jnp.int8))
    h = x + attention(p, u, cq, d, mask if select else None, mm)
    nx = rms_norm(h, p["ffn_norm"], d["eps"])
    if "router" in ffn:
        out, biased, own = routed_part(ffn, nx, d, chosen, mm)
        if "shared_gate" in ffn:
            out = out + shared_part(ffn, nx, mm)
        return h + out, mask, biased, own, scores
    f = {k: v.astype(F32) for k, v in ffn.items()}
    return (h + swiglu(nx, f["gate"], f["up"], f["down"], mm), mask, None,
            None, scores)


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, w, eps, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    w = w.astype(F32)
    return x @ w if quant is None else quant(x) @ quant(w)


def forward(params, tokens, desc, chosen=None, logits_at=None, quant=None,
            positions=None, select=True):
    """The full forward over one sequence ``tokens`` [T].

    ``chosen``: per layer, None or ``[T, k]`` expert ids to use in place
    of the router's own choice. ``logits_at``: the positions whose logits
    are returned (all when None). ``positions``: per ``full`` layer, in
    order, None or ``[n, index_topk]`` positions (``-1``: none) that the
    rows ``logits_at`` attend in place of their own choice (the
    program's, for the comparison under its choice). ``quant``: a function
    that rounds the operands of every matrix product (the control one
    precision down). ``select=False``: NO selection, every earlier
    position is attended (the control that must fail on a long prompt).
    Returns ``{"logits" [n, V], "biased", "own": per expert layer,
    "index_scores": per full layer [n, T], the reference's scores of the
    rows ``logits_at`` (``-inf`` above the diagonal)}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    T = len(tokens)
    at = jnp.arange(T) if logits_at is None else jnp.asarray(logits_at)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        biased, own, scores = [], [], []
        mask, full = None, 0
        for i, p in enumerate(params["layers"]):
            forced = None
            if "indexer" in p:
                mask = None
                if positions is not None and positions[full] is not None:
                    sets = np.asarray(positions[full])
                    hit = np.zeros((len(at), T), bool)
                    rows = np.repeat(np.arange(len(at)), sets.shape[1])
                    keep = sets.reshape(-1) >= 0
                    hit[rows[keep], sets.reshape(-1)[keep]] = True
                    forced = (at, jnp.asarray(hit))
                full += 1
            x, mask, b, o, sc = layer(
                p, x, dkey, None if chosen is None else chosen[i], mask,
                quant, select, at, forced)
            if b is not None:
                biased.append(b)
                own.append(o)
            if sc is not None:
                scores.append(sc)
        logits = head(x[at], params["final_norm"], params["head"], d["eps"],
                      quant)
    return {"logits": logits, "biased": biased, "own": own,
            "index_scores": scores}
