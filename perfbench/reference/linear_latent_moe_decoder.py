"""The plain reference of a decoder-only model of gated delta-rule LINEAR
attention layers (Kimi Delta Attention, arXiv:2510.26692) whose
full-attention sibling is a LATENT (MLA) layer with NO positional encoding
and no query compression, a leading dense layer, then routed experts (HF
``kimi_linear``): float32 ``jax.numpy`` at HIGHEST matmul precision, no
kernels, no cache, no pages, no chunks, no batching.

``x`` is ``[tokens, hidden]``; ``u`` a mixer's normed input; layers are
counted from 1, as the configuration counts them.

    block i: h = x + Mixer_i(RMSNorm_in(x));   y = h + FFN_i(RMSNorm_ff(h))
    FFN_i:   SwiGLU(intermediate_size) for i <= first_k_dense_replace, else
             Shared(x) + scale * sum_{j in top-k and HELD} w_j E_j(x);
             w the sigmoid scores of the k chosen (by score + selection
             bias over ALL the routed experts), normalised over the k
             (``moe_renormalize``)
    i in linear_attn_config.kda_layers: the delta-rule mixer of
             ``reference/linear_attn_moe_decoder.py`` AS IT IS (the
             recurrence a plain loop over t), with beta = sigmoid(u Wb):
             no factor 2 (this model has no ``kda_allow_neg_eigval``)
    i in linear_attn_config.full_attn_layers (H heads, EXPANDED form):
      q          = u Wq                            [H, dn + dr]
      [ckv|k_pe] = u Wkv_a;  row = [RMSNorm(ckv) | k_pe]   NO rotation
      [k_nope|v] = row[:C] Wkv_b                    a head [dn | dv]
      k          = [k_nope | k_pe]   (k_pe shared by the heads)
      out        = softmax(q k^T (dn + dr)^-1/2, causal) v Wo
    logits = RMSNorm_final(y_L) @ W_head

``rotate=theta`` is a CONTROL, not the model: q_pe and k_pe rotated at
their positions (split halves) as a latent layer WITH rotary would; a
program that rotates reads as this does.

The PARAMETERS' layout is the served program's, so that both sides hold
one copy (``weights_kimi.tree``): every matrix ``[in, out]``. The same HELD
shard of the experts and slice of the vocabulary as the served model.

The parameter tree (any float dtype, upcast here a layer, and an expert,
at a time)::

    {"embed" [V, D], "head" [D, V], "final_norm" [D],
     "layers": [{"in_norm", "ff_norm",
                 "mixer": {"q", "kv_a", "kv_norm", "kv_b", "o"}   # latent
                   or     {"qkv", "conv_w", "f_a", "f_b", "dt_bias",
                           "a_log", "beta", "g_a", "g_b", "g_bias",
                           "o_norm", "o"},
                 "ffn": {"gate", "up", "down"}                    # dense
                   or  {"router", "router_bias", "gate", "up", "down",
                        "shared_gate", "shared_up", "shared_down"}}]}
"""

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.linear_attn_moe_decoder import (
    QUERY_BLOCK,
    linear_attention,
)
from perfbench.reference.sparse_latent_moe_decoder import (
    _blocks,
    rms_norm,
    rope,
    routed_part,
    shared_part,
    swiglu,
)

F32 = jnp.float32


def dims(desc):
    shard = desc.get("expert_shard")
    lin = desc["linear_attn_config"]
    return dict(
        D=desc["hidden_size"], H=desc["num_attention_heads"],
        dn=desc["qk_nope_head_dim"], dr=desc["qk_rope_head_dim"],
        dv=desc["v_head_dim"], C=desc["kv_lora_rank"],
        Hl=lin["num_heads"], dl=lin["head_dim"],
        kw=lin["short_conv_kernel_size"],
        eps=float(desc["rms_norm_eps"]), k=desc["num_experts_per_token"],
        scale=float(desc["routed_scaling_factor"]),
        norm_topk=bool(desc["moe_renormalize"]),
        first=int(shard["first"]) if shard else 0, beta_scale=1.0)


def layer_kinds(desc):
    """``"latent"`` or ``"linear"`` a layer; the lists count from 1."""
    full = desc["linear_attn_config"]["full_attn_layers"]
    return ["latent" if i + 1 in full else "linear"
            for i in range(desc["num_hidden_layers"])]


def latent_row(p, u, d, mm=jnp.matmul, rotate=None):
    """The row a position caches ``[RMSNorm(ckv) | k_pe]`` [T, C + dr]."""
    kva = mm(u, p["kv_a"])
    k_pe = kva[:, d["C"]:]
    if rotate is not None:
        k_pe = rope(k_pe, jnp.arange(u.shape[0]), rotate)
    return jnp.concatenate(
        [rms_norm(kva[:, :d["C"]], p["kv_norm"], d["eps"]), k_pe], -1)


def latent_attention(p, u, d, mm=jnp.matmul, rotate=None):
    """Causal latent attention over the whole sequence in the expanded
    form, a block of queries at a time. Returns (out [T, D], rows)."""
    T, H, dn = u.shape[0], d["H"], d["dn"]
    pos = jnp.arange(T)
    q = mm(u, p["q"]).reshape(T, H, dn + d["dr"])
    row = latent_row(p, u, d, mm, rotate)
    kv = mm(row[:, :d["C"]], p["kv_b"]).reshape(T, H, dn + d["dv"])
    k_nope, v, k_pe = kv[..., :dn], kv[..., dn:], row[:, d["C"]:]
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    if rotate is not None:
        q_pe = rope(q_pe, pos, rotate)

    def block(at, qn, qp):
        s = (jnp.einsum("thn,shn->hts", qn, k_nope)
             + jnp.einsum("thr,sr->hts", qp, k_pe))
        s = s / jnp.sqrt(F32(dn + d["dr"]))
        s = jnp.where(pos[None, None, :] <= at[None, :, None], s, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v)

    out = _blocks(block, T, QUERY_BLOCK, pos, q_nope, q_pe)
    return mm(out.reshape(T, -1), p["o"]), row


@functools.partial(jax.jit, static_argnums=(2, 3, 6, 7, 8))
def layer(p, x, dkey, kind, record_at, chosen=None, quant=None,
          state_round=None, rotate=None):
    """One block on float32 ``x``; ``p`` is upcast here (the experts one
    at a time). ``quant`` rounds every matrix product's operands,
    ``state_round`` the state after every token, ``rotate`` applies RoPE
    at that theta to a latent layer (the controls). Returns (y, S or the
    latent rows, s + b or None, the router's own choice or None)."""
    d = dict(dkey)
    mm = jnp.matmul if quant is None else (
        lambda a, b: jnp.matmul(quant(a), quant(b)))
    mixer = {k: v.astype(F32) for k, v in p["mixer"].items()}
    u = rms_norm(x, p["in_norm"].astype(F32), d["eps"])
    if kind == "latent":
        out, kept = latent_attention(mixer, u, d, mm, rotate)
    else:
        out, kept = linear_attention(mixer, u, d, record_at, mm,
                                     state_round)
    h = x + out
    nx = rms_norm(h, p["ff_norm"].astype(F32), d["eps"])
    ffn = p["ffn"]
    if "router" not in ffn:
        f = {k: v.astype(F32) for k, v in ffn.items()}
        return (h + swiglu(nx, f["gate"], f["up"], f["down"], mm), kept,
                None, None)
    routed, biased, own = routed_part(ffn, nx, d, chosen, mm)
    return h + routed + shared_part(ffn, nx, mm), kept, biased, own


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, norm, w, eps, quant=None):
    x = rms_norm(x, norm.astype(F32), eps)
    w = w.astype(F32)
    return x @ w if quant is None else quant(x) @ quant(w)


def forward(params, tokens, desc, chosen=None, logits_at=None, states_at=(),
            quant=None, state_round=None, rotate=None):
    """The full forward over one sequence ``tokens`` [T].

    ``chosen``: per layer, None or ``[T, k]`` expert ids to use in place
    of the router's own choice (None for a dense layer). ``logits_at``:
    the positions whose logits are returned (all when None).
    ``states_at``: positions after which every linear layer's ``S`` is
    returned. ``quant`` / ``state_round`` / ``rotate``: the controls (None
    is the model). Returns ``{"logits" [n, V], "biased", "own": per EXPERT
    layer, "states": [per linear layer, [len(states_at), H, dk, dv]],
    "rows": [per latent layer, [T, C + dr]]}``.
    """
    d = dims(desc)
    dkey = tuple(sorted(d.items()))
    record_at = jnp.asarray(list(states_at) or [0], jnp.int32)
    states, rows, biased, own = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for i, (p, kind) in enumerate(zip(params["layers"],
                                          layer_kinds(desc))):
            x, kept, b, o = layer(
                p, x, dkey, kind, record_at,
                None if chosen is None else chosen[i], quant, state_round,
                rotate)
            (rows if kind == "latent" else states).append(kept)
            if b is not None:
                biased.append(b)
                own.append(o)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        logits = head(x, params["final_norm"], params["head"], d["eps"],
                      quant)
    return {"logits": logits, "biased": biased, "own": own,
            "states": states, "rows": rows}
