"""Mixture-of-Experts FFN with expert parallelism.

The reference framework predates MoE entirely (like long-context —
SURVEY.md §5.7); this is the TPU-native design that provides the expert
(ep) axis of the parallelism story. Switch-Transformer-style routing in
fully static shapes (XLA requirement): top-1/top-2 gating, a fixed
per-expert capacity, einsum dispatch/combine tensors instead of
scatter/gather, and the load-balancing auxiliary loss.

Expert parallelism falls out of GSPMD: the stacked expert weights
[E, ...] are sharded on dim 0 over a mesh axis
(ParallelExecutor(sharding_overrides={"...moe...w": ("expert", ...)})),
the [E, C, D] dispatched activations inherit that sharding, and XLA
inserts the all-to-alls — no hand-written token exchange.

Routing is non-differentiable by design (argmax); gradients flow through
the gate probabilities via the combine weights, exactly the Switch
Transformer formulation.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op

_ACTS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "identity": lambda v: v,
}


def _route_one(probs, base, capacity, valid=None):
    """Route each token to its best remaining expert. probs: [N, E]
    (zeroed at experts already used by earlier routes); base: [E] queue
    occupancy from earlier routes; valid: optional [N] token validity
    (invalid tokens occupy no queue slots). Returns (expert_idx [N],
    gate [N], gate_raw [N], dispatch [N, E, C] one-hot with
    over-capacity tokens dropped, new base)."""
    n, e = probs.shape
    expert = jnp.argmax(probs, axis=-1)  # [N]
    gate = jnp.max(probs, axis=-1)
    onehot = jax.nn.one_hot(expert, e, dtype=probs.dtype)  # [N, E]
    if valid is not None:
        onehot = onehot * valid[:, None]
    # Position of each token within its expert's queue, in token order —
    # the static-shape stand-in for a scatter with overflow dropping.
    # Earlier routes' assignments (incl. dropped ones) advance the queue,
    # so routes never collide in the [E, C] buffer.
    pos = jnp.cumsum(onehot, axis=0) - onehot + base[None, :]  # [N, E]
    pos_tok = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [N]
    keep = pos_tok < capacity
    dispatch = (
        onehot[:, :, None]
        * jax.nn.one_hot(pos_tok, capacity, dtype=probs.dtype)[:, None, :]
        * keep[:, None, None]
    )  # [N, E, C]
    return (expert, gate * keep, gate, dispatch,
            base + jnp.sum(onehot, axis=0))


def _lower_moe_ffn(ctx, ins, attrs):
    x = ins["X"][0]  # [B, T, D] or [N, D]
    gate_w = ins["GateW"][0]  # [D, E]
    w1 = ins["ExpertW1"][0]  # [E, D, H]
    b1 = ins["ExpertB1"][0]  # [E, H]
    w2 = ins["ExpertW2"][0]  # [E, H, D]
    b2 = ins["ExpertB2"][0]  # [E, D]
    tok_mask = ins.get("Mask", [None])[0]  # optional [B, T] validity
    top_k = int(attrs.get("top_k", 1))
    cap_factor = float(attrs.get("capacity_factor", 1.25))
    act = _ACTS[attrs.get("act", "gelu")]

    orig_shape = jnp.shape(x)
    d = orig_shape[-1]
    xf = jnp.reshape(x, (-1, d))  # [N, D]
    n = xf.shape[0]
    e = gate_w.shape[1]
    capacity = max(1, int(cap_factor * n * top_k / e))

    logits = (xf @ gate_w).astype(jnp.float32)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    if tok_mask is not None:
        # Padding tokens must not route: they would consume shared expert
        # capacity (dropping REAL tokens' outputs) and dominate the
        # load-balancing statistics. Zeroing their probs gives them gate
        # 0 everywhere; _route_one's onehot is also zeroed below so they
        # occupy no queue slots.
        valid = (jnp.reshape(tok_mask, (-1,)) > 0).astype(probs.dtype)
        probs = probs * valid[:, None]
    else:
        valid = None

    combines = []
    used = jnp.zeros_like(probs)
    masked = probs
    base = jnp.zeros((e,), probs.dtype)
    for _ in range(top_k):
        expert, gate, gate_raw, dispatch, base = _route_one(
            masked, base, capacity, valid)
        combines.append((gate, gate_raw, dispatch))
        used = used + jax.nn.one_hot(expert, e, dtype=probs.dtype)
        masked = probs * (1.0 - used)
    if top_k > 1:
        # Switch/GShard renormalization: divide by the sum of the
        # SELECTED (pre-drop) gates, so a token whose second route
        # overflowed keeps weight g1/(g1+g2) on the surviving expert —
        # not full weight 1.0.
        total = sum(g_raw for _, g_raw, _ in combines) + 1e-9
        combines = [(g / total, g_raw, disp)
                    for g, g_raw, disp in combines]

    # One dispatch/combine pair covers all k routes.
    dispatch = sum(disp for _, _, disp in combines)  # [N, E, C]
    combine = sum(
        g[:, None, None] * disp for g, _, disp in combines
    )  # [N, E, C]

    xe = jnp.einsum(
        "nec,nd->ecd", dispatch.astype(x.dtype), xf
    )  # [E, C, D]
    h = act(
        jnp.einsum("ecd,edh->ech", xe, w1) + b1[:, None, :]
    )
    ye = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]  # [E, C, D]
    out = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), ye)

    # Switch load-balancing loss: E * sum_e f_e * P_e, where f_e is the
    # fraction of tokens whose TOP-1 router choice is expert e — the
    # PRE-capacity-drop assignment (switch_transformer paper eq. 4).
    # Computing f from the post-drop dispatch would cap it at
    # capacity/N, saturating the loss exactly when routing collapses
    # onto one expert and it needs the strongest push. With a token
    # mask, both statistics run over VALID tokens only.
    top1 = jnp.argmax(probs, axis=-1)
    oh1 = jax.nn.one_hot(top1, e, dtype=jnp.float32)
    if valid is not None:
        oh1 = oh1 * valid[:, None]
        denom = jnp.maximum(jnp.sum(valid), 1.0)
    else:
        denom = float(n)
    f = jnp.sum(oh1, axis=0) / denom
    p = jnp.sum(probs, axis=0) / denom
    aux = e * jnp.sum(f * p)

    return {
        "Out": jnp.reshape(out, orig_shape),
        "AuxLoss": jnp.reshape(aux.astype(x.dtype), (1,)),
    }


register_op(
    "moe_ffn",
    inputs=["X", "GateW", "ExpertW1", "ExpertB1", "ExpertW2", "ExpertB2",
            "Mask"],
    outputs=["Out", "AuxLoss"],
    attrs={"top_k": 1, "capacity_factor": 1.25, "act": "gelu"},
    lower=_lower_moe_ffn,
    grad="auto",
    no_grad_inputs=("Mask",),
)


# -- dropless routed experts --------------------------------------------------

def route_top_k(x, router_w, router_bias, top_k, norm_topk, scale):
    """Sigmoid-score routing with a selection bias (``noaux_tc`` with one
    group): ``s = sigmoid(float32(x) float32(Wr))``; the ``top_k`` experts
    with the largest ``s + b`` are chosen; the weights are the chosen
    ``s`` (not ``s + b``), normalised over the chosen when ``norm_topk``,
    times ``scale``. The router runs in float32 at full precision,
    whatever the activations' dtype. Returns (chosen [N, k] int32,
    weights [N, k] float32)."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + router_bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * scale


def dropless_experts(x, chosen, weights, w_gate, w_up, w_down, valid=None,
                     first=None):
    """Every (token, chosen expert) pair is computed: the pairs are
    sorted by expert and each expert's rows go through its gated FFN as
    ONE group of three grouped matrix products
    (``kernels/grouped_matmul.py``: a Pallas kernel on the TPU,
    ``jax.lax.ragged_dot`` elsewhere), so
    no capacity exists and no token can be dropped. Rows of tokens that
    are not ``valid`` (a prefill batch's padding, a decode step's empty
    slots) sort behind every group and are not computed. With ``first``
    the weights are a SHARD of the experts, ``first .. first + E - 1`` of
    those the router chose among: a pair whose expert is not held sorts
    behind the groups like a token that does not exist, and its part of
    the sum is left out (the chip that holds the expert adds it). Returns
    (the weighted sum [N, D] in float32, tokens each held expert got
    [E])."""
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul

    def product(lhs, rhs):
        return grouped_matmul(lhs, rhs, counts)

    n, k = chosen.shape
    e = w_gate.shape[0]
    flat = chosen.reshape(-1)
    if first is not None:
        flat = flat - first
        held = (flat >= 0) & (flat < e)
        if valid is not None:
            held = held & jnp.repeat(valid, k)
        flat = jnp.where(held, flat, e)
    elif valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
    xs = x[order // k]                                       # [N*k, D]
    h = jax.nn.silu(product(xs, w_gate)) * product(xs, w_up)
    y = product(h.astype(x.dtype), w_down)
    y = y * weights.reshape(-1)[order][:, None]
    if valid is not None or first is not None:
        # rows past the last group are not written by the grouped product
        y = jnp.where((jnp.arange(n * k) < jnp.sum(counts))[:, None], y, 0.0)
    # back to (token, rank) order: a gather through the inverse permutation
    inverse = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    return y[inverse].reshape(n, k, -1).sum(axis=1), counts


# tokens a block of a held shard's dispatch (16384 token places x 8
# choices would sort 131072 rows of 6144 for the ~8192 that are held)
_HELD_TOKEN_BLOCK = 2048


def _lower_dropless_moe_ffn(ctx, ins, attrs):
    from paddle_tpu.ops.decoder_ops import gated_ffn

    x = ins["X"][0]                                          # [N, D]
    valid = ins.get("Valid", [None])[0]
    if valid is not None:
        valid = jnp.reshape(valid, (-1,)) > 0
    scoring = attrs.get("scoring", "sigmoid")
    with jax.named_scope("dropless_route"):
        if scoring == "softmax_topk":
            chosen, weights = route_softmax_top_k(
                x, ins["RouterW"][0], int(attrs["top_k"]))
        elif scoring == "softmax":
            chosen, weights = route_softmax(
                x, ins["RouterW"][0], ins["RouterBias"][0],
                int(attrs["top_k"]), float(attrs.get("scale", 1.0)))
        else:
            chosen, weights = route_top_k(
                x, ins["RouterW"][0], ins["RouterBias"][0],
                int(attrs["top_k"]), bool(attrs.get("norm_topk", True)),
                float(attrs.get("scale", 1.0)))
    first = int(attrs.get("held_first", -1))
    first = None if first < 0 else first
    zero = int(attrs.get("zero_experts", 0))
    if zero:
        # the router's last ``zero`` outputs are identities: such a choice
        # is the token itself times its weight, computed here for every
        # token whoever holds the real experts. It has no group among the
        # sorted pairs: its index lies past every held expert's, so with
        # ``first`` (0 where all the real experts are held) it sorts
        # behind the groups like a pair held elsewhere
        real = ins["RouterW"][0].shape[-1] - zero
        first = 0 if first is None else first
        is_zero = chosen >= real
        if valid is not None:
            is_zero = is_zero & valid[:, None]
        identity = jnp.sum(jnp.where(is_zero, weights, 0.0), axis=-1)
    # tokens a block: the op's own where a model with many choices a token
    # asks for fewer (the sorted copies are ``block * top_k`` rows)
    block = int(attrs.get("token_block", 0)) or _HELD_TOKEN_BLOCK
    n = x.shape[0] // block
    with jax.named_scope("dropless_experts"):
        experts = (ins["ExpertWGate"][0], ins["ExpertWUp"][0],
                   ins["ExpertWDown"][0])
        if first is None or n < 2 or x.shape[0] % block:
            routed, counts = dropless_experts(x, chosen, weights, *experts,
                                              valid=valid, first=first)
        else:
            # a shard's pairs are few among the rows sorted for them: a
            # large dispatch's tokens go through in blocks, so that the
            # sorted copies stay ``block * top_k`` rows whatever it holds
            def one(part):
                return dropless_experts(part[0], part[1], part[2], *experts,
                                        valid=part[3], first=first)

            live = (jnp.ones((x.shape[0],), bool) if valid is None
                    else valid)
            routed, counts = jax.lax.map(one, tuple(
                a.reshape((n, block) + a.shape[1:])
                for a in (x, chosen, weights, live)))
            routed = routed.reshape((x.shape[0],) + routed.shape[2:])
            counts = counts.sum(axis=0)
    out = routed
    if zero:
        out = out + identity[:, None] * x.astype(jnp.float32)
    if ins.get("SharedWGate"):
        # the shared expert sees every token once, outside the routing
        out = out + gated_ffn(x, ins["SharedWGate"][0], ins["SharedWUp"][0],
                              ins["SharedWDown"][0]).astype(jnp.float32)
    outs = {"Out": out.astype(x.dtype), "Chosen": chosen,
            "ExpertTokens": counts}
    if zero:
        outs["ZeroTokens"] = jnp.sum(is_zero, dtype=jnp.int32).reshape(1)
    return outs


register_op(
    "dropless_moe_ffn",
    inputs=["X", "RouterW", "RouterBias", "ExpertWGate", "ExpertWUp",
            "ExpertWDown", "SharedWGate", "SharedWUp", "SharedWDown",
            "Valid"],
    outputs=["Out", "Chosen", "ExpertTokens", "ZeroTokens"],
    # held_first >= 0: the expert weights are the shard that starts there
    # (the router keeps all its outputs); scoring "softmax_topk" is the
    # second rule (``route_softmax_top_k``: no bias is read), "softmax"
    # the third (``route_softmax``); zero_experts: the router's last
    # outputs that are identities (``ZeroTokens``: the valid tokens'
    # choices that fell on one); token_block: the tokens a block of a held
    # shard's dispatch (0: ``_HELD_TOKEN_BLOCK``)
    attrs={"top_k": 1, "norm_topk": True, "scale": 1.0, "held_first": -1,
           "scoring": "sigmoid", "zero_experts": 0, "token_block": 0},
    lower=_lower_dropless_moe_ffn,
    grad=None,
)


def route_softmax_top_k(x, router_w, top_k):
    """The ``top_k`` largest of the router's raw logits, a softmax over
    those ``top_k`` logits alone (HF ``GraniteMoeTopKGating``): no bias, no
    scale. Float32 at full precision, as ``route_top_k``. Returns (chosen
    [N, k] int32, weights [N, k] float32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, chosen = jax.lax.top_k(logits, top_k)
    return chosen.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_softmax(x, router_w, router_bias, top_k, scale):
    """A softmax over ALL the router's outputs (HF
    ``LongcatFlashTopkRouter``): ``p = softmax(float32(x) float32(Wr))``;
    the ``top_k`` outputs with the largest ``p + b`` are chosen (the bias
    moves the choice alone); the weights are ``scale * p`` of the chosen,
    NOT renormalised over them. Float32 at full precision, as
    ``route_top_k``. Returns (chosen [N, k] int32, weights [N, k]
    float32)."""
    p = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, chosen = jax.lax.top_k(p + router_bias.astype(jnp.float32), top_k)
    return (chosen.astype(jnp.int32),
            jnp.take_along_axis(p, chosen, axis=-1) * scale)
