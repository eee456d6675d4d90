"""Operations and bytes that the ALGORITHM of each kernel of the shortcut
decoder needs (``longcat_flash_omni_4l``: two latent-attention blocks and
two dense feed-forwards a layer, the expert block across them), from its
shapes and the configuration's own keys: the least the mathematics asks
for (a held expert that got a token read once a call, each resident latent
row once for all heads, a choice that fell on an identity NOTHING), so a
share of a roofline cannot pass 100%. A multiply-add is two operations;
parameters, rows and activations are bfloat16 (2 bytes). A share is
``least seconds / measured seconds``.
"""

from perfbench import kernel_costs_glm as glm

ITEM = glm.ITEM  # bytes of a bfloat16
least_seconds = glm.least_seconds


def _d(cfg):
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], rq=cfg["q_lora_rank"], C=cfg["kv_lora_rank"],
        F=cfg["ffn_hidden_size"], Fe=cfg["expert_ffn_hidden_size"],
        E=cfg["n_routed_experts"], Er=cfg["expert_shard"]["of"],
        Z=cfg["zero_expert_num"], k=cfg["moe_topk"], L=cfg["num_layers"],
        V=cfg["vocab_size"])


def attention_parameters(cfg):
    """ONE attention block: q_a, q_b, kv_a, kv_b, o, the two low-rank
    norms (a layer has two)."""
    d = _d(cfg)
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["C"] + d["dr"])
            + d["C"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * d["D"] + d["rq"] + d["C"])


def expert_parameters(cfg):
    """One real routed expert (gate, up, down)."""
    d = _d(cfg)
    return 3 * d["D"] * d["Fe"]


def parameter_count(cfg):
    """The configuration AS IT IS RUN: its own depth, the real experts
    HELD, the router's every output, the vocabulary's slice."""
    d = _d(cfg)
    attn = attention_parameters(cfg)
    dense = 3 * d["D"] * d["F"]
    router = d["D"] * (d["Er"] + d["Z"]) + d["Er"] + d["Z"]
    outside = 2 * (attn + dense) + 4 * d["D"] + router
    held = d["E"] * expert_parameters(cfg)
    emb = d["V"] * d["D"]
    return {"attention_block": attn, "dense_ffn": dense, "router": router,
            "layer_outside_experts": outside,
            "routed_expert": expert_parameters(cfg), "held_experts": held,
            "embedding": emb, "head": emb,
            "total": 2 * emb + d["D"] + d["L"] * (outside + held)}


def cached_bytes_per_token(cfg):
    """Two latent rows a layer (one an attention block): ``kv_lora_rank +
    qk_rope_head_dim`` wide each."""
    d = _d(cfg)
    return 2 * d["L"] * (d["C"] + d["dr"]) * ITEM


def expected_experts_hit(cfg, pairs):
    """Held experts that get at least one of ``pairs`` (token, router
    output) choices spread evenly over all the router's outputs."""
    d = _d(cfg)
    return d["E"] * (1.0 - (1.0 - 1.0 / (d["Er"] + d["Z"])) ** pairs)


def decode_step_bytes(cfg, resident_rows, experts_hit):
    """Bytes ONE decode token step must read: every parameter outside the
    routed experts but the embedding table once, the weights of the
    ``experts_hit`` held experts a layer that got a token, and the
    ``resident_rows`` latent rows of each of the ``2 L`` pools once."""
    d = _d(cfg)
    count = parameter_count(cfg)
    fixed = (count["total"] - count["embedding"]
             - d["L"] * count["held_experts"])
    return ((fixed + d["L"] * experts_hit * count["routed_expert"]) * ITEM
            + resident_rows * cached_bytes_per_token(cfg))


def latent_decode_attention(cfg, rows, queries):
    """(operations, bytes) of ONE attention block's absorbed-form decode
    attention: ``queries`` slots over ``rows`` cached rows IN TOTAL. Each
    row is read once for all heads (scores over the whole row, values over
    its latent part); the latent queries and outputs are read and written
    once."""
    d = _d(cfg)
    W = d["C"] + d["dr"]
    ops = 2.0 * d["H"] * (W + d["C"]) * rows
    moved = (rows * W + queries * d["H"] * (W + d["C"])) * ITEM
    return ops, moved


def prefill_attention(cfg, lengths):
    """(operations, bytes) of ONE attention block's causal prefill
    attention over prompts of ``lengths`` (expanded form: queries and keys
    ``dn + dr`` wide, values ``dv``): the lower triangle's two products at
    their OWN widths (no lane of padding is counted), q, k, v read and the
    output written once."""
    d = _d(cfg)
    dq = d["dn"] + d["dr"]
    pairs = sum(n * (n + 1) / 2.0 for n in lengths)
    ops = 2.0 * d["H"] * pairs * (dq + d["dv"])
    moved = sum(lengths) * d["H"] * (2 * dq + 2 * d["dv"]) * ITEM
    return ops, moved


def expert_matmuls(cfg, held_pairs, experts_hit):
    """(operations, bytes) of ONE layer's three grouped products over the
    ``held_pairs`` (token, expert) rows that fell on held real experts,
    ``experts_hit`` of which got any. A choice that fell on an identity or
    on an expert held elsewhere is no row here."""
    d = _d(cfg)
    ops = 2.0 * held_pairs * expert_parameters(cfg)
    moved = (experts_hit * expert_parameters(cfg)
             + 2.0 * held_pairs * d["D"]) * ITEM
    return ops, moved
