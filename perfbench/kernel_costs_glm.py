"""Operations and bytes that the ALGORITHM of each new kernel of the
latent-attention, routed-expert decoder needs, from its shapes and the
configuration's own keys: the least the mathematics asks for (every held
expert's weights once a call, each live row once), so a share of a
roofline cannot pass 100%. A multiply-add is two operations; parameters,
rows and activations are bfloat16 (2 bytes). ``kernel_costs.py`` holds the
Transformer's; a share is ``least seconds / measured seconds``.
"""

ITEM = 2  # bytes of a bfloat16


def _d(cfg):
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], rq=cfg["q_lora_rank"], C=cfg["kv_lora_rank"],
        F=cfg["intermediate_size"], Fm=cfg["moe_intermediate_size"],
        E=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        shared=cfg.get("n_shared_experts", 0),
        dense=cfg.get("first_k_dense_replace", 0),
        L=cfg["num_hidden_layers"], V=cfg["vocab_size"])


def attention_parameters(cfg):
    """One layer's attention: q_a, q_b, kv_a, kv_b, o, the two low-rank
    norms."""
    d = _d(cfg)
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["C"] + d["dr"])
            + d["C"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * d["D"] + d["rq"] + d["C"])


def expert_parameters(cfg):
    """One routed expert (gate, up, down)."""
    d = _d(cfg)
    return 3 * d["D"] * d["Fm"]


def parameter_count(cfg):
    """{"embedding", "head", "dense_layer", "expert_layer_outside_experts",
    "routed_experts_a_layer", "total"} of the configuration AS IT IS RUN
    (its own ``num_hidden_layers``)."""
    d = _d(cfg)
    attn = attention_parameters(cfg) + 2 * d["D"]      # + the two block norms
    dense = attn + 3 * d["D"] * d["F"]
    outside = (attn + d["shared"] * expert_parameters(cfg)
               + d["D"] * d["E"] + d["E"])             # router and its bias
    routed = d["E"] * expert_parameters(cfg)
    n_moe = d["L"] - d["dense"]
    emb = d["V"] * d["D"]
    return {"embedding": emb, "head": emb, "dense_layer": dense,
            "expert_layer_outside_experts": outside,
            "routed_experts_a_layer": routed,
            "total": (2 * emb + d["D"] + d["dense"] * dense
                      + n_moe * (outside + routed))}


def cached_bytes_per_token(cfg):
    """One latent row a layer: ``kv_lora_rank + qk_rope_head_dim`` wide."""
    d = _d(cfg)
    return d["L"] * (d["C"] + d["dr"]) * ITEM


def decode_step_bytes(cfg, live_rows):
    """Bytes ONE decode token step must read: every parameter but the
    embedding table once (the step gathers only the live tokens' rows of
    it) and the live latent rows of every layer once."""
    count = parameter_count(cfg)
    return ((count["total"] - count["embedding"]) * ITEM
            + live_rows * cached_bytes_per_token(cfg))


def expert_matmuls(cfg, pairs):
    """(operations, bytes) of ONE layer's three grouped products over
    ``pairs`` (token, expert) rows: each expert that can have got a row
    has its weights read once, the rows go in once and come out once
    (the gate/up intermediate can stay on the chip)."""
    d = _d(cfg)
    ops = 2.0 * pairs * expert_parameters(cfg)
    moved = (min(d["E"], pairs) * expert_parameters(cfg)
             + 2.0 * pairs * d["D"]) * ITEM
    return ops, moved


def latent_decode_attention(cfg, rows, queries):
    """(operations, bytes) of ONE layer's absorbed-form decode attention:
    ``queries`` slots over ``rows`` cached rows IN TOTAL. Each row is read
    once for all heads (scores over the whole row, values over its latent
    part); the latent queries and outputs are read and written once."""
    d = _d(cfg)
    W = d["C"] + d["dr"]
    ops = 2.0 * d["H"] * (W + d["C"]) * rows
    moved = (rows * W + queries * d["H"] * (W + d["C"])) * ITEM
    return ops, moved


def prefill_attention(cfg, lengths):
    """(operations, bytes) of ONE layer's causal prefill attention over
    prompts of ``lengths`` (expanded form, head width ``dn + dr`` = the
    value width): the lower triangle's products, q, k, v read and the
    output written once."""
    d = _d(cfg)
    dq = d["dn"] + d["dr"]
    pairs = sum(n * (n + 1) / 2.0 for n in lengths)
    ops = 2.0 * d["H"] * pairs * (dq + d["dv"])
    moved = sum(lengths) * d["H"] * (2 * dq + 2 * d["dv"]) * ITEM
    return ops, moved


def least_seconds(ops, moved, peaks):
    """The roofline: the larger of operations over the peak rate and
    bytes over the peak bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
