"""Plain reference: the pre-norm Transformer encoder-decoder of Vaswani et
al. 2017 (arXiv:1706.03762) as this repo's ``transformer_*`` configurations
state it, in straightforward ``jax.numpy``.

No kernels, no cache, no batching tricks, float32 with
``jax.default_matmul_precision("highest")``. Independent of
``paddle_tpu/models/transformer.py``: it shares no code with it and takes
only a dict of weights that the benchmark made from the seed
(``perfbench/weights.py``) under this file's own names.

Departures from the paper, which the configurations state too: pre-norm
residual blocks (LayerNorm before each sub-layer, one more after the last
layer of each stack), no bias on the four attention projections, separate
source/target embeddings and an untied output projection with a bias, the
sinusoid table as ``[sin | cos]`` halves rather than interleaved.

``quant`` is the hook of the lower-precision control: a function applied to
BOTH operands of every matrix product (and to the attention operands). The
sound reference passes ``None``.
"""

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
NEG = -1e9


def position_table(length, d_model):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    i = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2.0 * i / d_model)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b)


def layer_norm(x, scale, bias, act):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return act((x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias)


def attention(xq, xkv, w, n_head, bias, quant, act):
    """Multi-head attention. ``xq`` [B, Tq, D], ``xkv`` [B, Tk, D]; ``bias``
    broadcastable to [B, H, Tq, Tk], added to the scaled scores."""
    B, Tq, D = xq.shape
    Tk = xkv.shape[1]
    dh = D // n_head

    def heads(x, T):
        return x.reshape(B, T, n_head, dh).transpose(0, 2, 1, 3)

    q = heads(act(_mm(xq, w["q"], quant)), Tq)
    k = heads(act(_mm(xkv, w["k"], quant)), Tk)
    v = heads(act(_mm(xkv, w["v"], quant)), Tk)
    if quant is not None:
        q, k, v = quant(q), quant(k), quant(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh) + bias
    p = act(jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            .astype(scores.dtype))
    if quant is not None:
        p = quant(p)
    ctx = act(jnp.einsum("bhqk,bhkd->bhqd", p, v))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, Tq, D)
    return act(_mm(ctx, w["o"], quant))


def ffn(x, w, quant, act):
    h = act(jax.nn.relu(_mm(x, w["w1"], quant) + w["b1"]))
    return act(_mm(h, w["w2"], quant) + w["b2"])


def _embed(ids, table, d_model, act):
    x = table[ids] * math.sqrt(d_model)
    return act(x + position_table(ids.shape[1], d_model)[None])


def encode(params, src, src_len, n_head, quant=None, act=lambda x: x):
    """Encoder output [B, S, D] and the key-padding bias [B, 1, 1, S]."""
    D = params["src_emb"].shape[1]
    S = src.shape[1]
    valid = jnp.arange(S)[None, :] < src_len.reshape(-1, 1)
    bias = jnp.where(valid, 0.0, NEG)[:, None, None, :]
    x = _embed(src, params["src_emb"], D, act)
    for lw in params["enc"]:
        h = layer_norm(x, *lw["attn_ln"], act)
        x = act(x + attention(h, h, lw["attn"], n_head, bias, quant, act))
        x = act(x + ffn(layer_norm(x, *lw["ffn_ln"], act), lw["ffn"],
                        quant, act))
    return layer_norm(x, *params["enc_final_ln"], act), bias


def decode(params, enc, cross_bias, trg, n_head, quant=None,
           act=lambda x: x):
    """Decoder logits [B, T, V] over the whole target ``trg`` [B, T]."""
    D = params["trg_emb"].shape[1]
    T = trg.shape[1]
    causal = jnp.where(jnp.tril(jnp.ones((T, T), bool)), 0.0,
                       NEG)[None, None]
    x = _embed(trg, params["trg_emb"], D, act)
    for lw in params["dec"]:
        h = layer_norm(x, *lw["self_ln"], act)
        x = act(x + attention(h, h, lw["self"], n_head, causal, quant, act))
        x = act(x + attention(layer_norm(x, *lw["cross_ln"], act), enc,
                              lw["cross"], n_head, cross_bias, quant, act))
        x = act(x + ffn(layer_norm(x, *lw["ffn_ln"], act), lw["ffn"],
                        quant, act))
    x = layer_norm(x, *params["dec_final_ln"], act)
    return _mm(x, params["proj_w"], quant) + params["proj_b"]


def forward(params, src, src_len, trg, n_head, quant=None,
            act=lambda x: x):
    enc, bias = encode(params, src, src_len, n_head, quant, act)
    return decode(params, enc, bias, trg, n_head, quant, act)


def loss_fn(params, batch, n_head, label_smooth_eps, quant=None,
            act=lambda x: x):
    """Label-smoothed cross entropy, averaged over non-pad target
    positions (the paper's section 5.4 regularisation, eps_ls = 0.1)."""
    logits = forward(params, batch["src_word"], batch["src_len"],
                     batch["trg_word"], n_head, quant, act)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    V = logits.shape[-1]
    hard = -jnp.take_along_axis(logp, batch["label"][..., None],
                                axis=-1)[..., 0]
    cost = ((1.0 - label_smooth_eps) * hard
            + (label_smooth_eps / V) * (-jnp.sum(logp, axis=-1)))
    T = logits.shape[1]
    mask = (jnp.arange(T)[None, :]
            < batch["trg_len"].reshape(-1, 1)).astype(jnp.float32)
    return jnp.sum(cost * mask) / jnp.sum(mask)


def _identity(x):
    return x


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _loss_and_grads(params, batch, n_head, label_smooth_eps, quant, act):
    return jax.value_and_grad(loss_fn)(params, batch, n_head,
                                       label_smooth_eps, quant, act)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _logits(params, src, src_len, trg, n_head, quant, act):
    return forward(params, src, src_len, trg, n_head, quant, act)


def loss_and_grads(params, batch, n_head, label_smooth_eps, quant=None,
                   act=_identity):
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(params, batch, n_head, label_smooth_eps,
                               quant, act)


def logits(params, src, src_len, trg, n_head, quant=None, act=_identity):
    with jax.default_matmul_precision("highest"):
        return _logits(params, src, src_len, trg, n_head, quant, act)
