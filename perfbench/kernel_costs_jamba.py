"""Operations and bytes that the ALGORITHM of each new kernel of the
hybrid state-space decoder needs, from its shapes and the configuration's
own keys: the least the mathematics asks for (a live slot's state read
once and written once a token, each real token's rows once), so a share of
a roofline cannot pass 100%. A multiply-add is two operations; parameters,
K/V rows, the convolution's window and activations are bfloat16 (2 bytes),
the recurrent state, Delta, B and C float32 (4). ``kernel_costs.py`` and
``kernel_costs_glm.py`` hold the other families'; a share is ``least
seconds / measured seconds``.
"""

ITEM, F32 = 2, 4  # bytes of a bfloat16, of a float32


def _d(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds = layer_kinds(cfg)
    return dict(
        D=D, H=H, Hkv=cfg["num_key_value_heads"], dh=D // H,
        F=cfg["intermediate_size"], V=cfg["vocab_size"],
        d=cfg["mamba_expand"] * D, n=cfg["mamba_d_state"],
        kw=cfg["mamba_d_conv"], r=cfg["mamba_dt_rank"],
        mamba=kinds.count("mamba"), attention=kinds.count("attention"))


def layer_kinds(cfg):
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def parameter_count(cfg):
    """{"embedding" (tied: it is the head too), "mamba_mixer",
    "attention_mixer", "mlp", "mamba_layer", "attention_layer", "total"}
    of the configuration as it is run."""
    d = _d(cfg)
    D, di, n, r = d["D"], d["d"], d["n"], d["r"]
    mamba = (D * 2 * di + di * D                    # in_proj, out_proj
             + di * (r + 2 * n) + r * di            # x_proj, dt_proj
             + n * di + di + d["kw"] * di + di      # A_log, D, conv w + b
             + di + r + 2 * n)                      # dt bias, inner norms
    attention = 2 * D * d["H"] * d["dh"] + 2 * D * d["Hkv"] * d["dh"]
    mlp = 3 * D * d["F"]
    emb = d["V"] * D
    mamba_layer, attention_layer = (mamba + mlp + 2 * D,
                                    attention + mlp + 2 * D)
    return {"embedding": emb, "mamba_mixer": mamba,
            "attention_mixer": attention, "mlp": mlp,
            "mamba_layer": mamba_layer, "attention_layer": attention_layer,
            "total": (emb + D + d["mamba"] * mamba_layer
                      + d["attention"] * attention_layer)}


def state_bytes_per_slot(cfg):
    """A slot's fixed-size state: per state-space layer the float32
    ``s`` [n, d] and the convolution's bfloat16 window [kw - 1, d]."""
    d = _d(cfg)
    return d["mamba"] * (d["n"] * d["d"] * F32
                         + (d["kw"] - 1) * d["d"] * ITEM)


def cached_bytes_per_token(cfg):
    """One K and one V row of every key/value head an attention layer."""
    d = _d(cfg)
    return d["attention"] * 2 * d["Hkv"] * d["dh"] * ITEM


def decode_step_bytes(cfg, live_slots, live_rows):
    """Bytes ONE decode token step must move: every parameter once (the
    tied embedding is read whole as the head), the LIVE slots' recurrent
    state and window read once and written once, the live K/V rows once."""
    return (parameter_count(cfg)["total"] * ITEM
            + 2 * live_slots * state_bytes_per_slot(cfg)
            + live_rows * cached_bytes_per_token(cfg))


def state_update(cfg, slots):
    """(operations, bytes) of ONE layer's one-token state update of
    ``slots`` slots: ``s`` read and written once, x, Delta, B, C read and
    y written once; three multiply-adds a state element (the decay, the
    input, the read-out; the exp is not a product)."""
    d = _d(cfg)
    elems = slots * d["n"] * d["d"]
    moved = (2 * elems * F32 + slots * d["d"] * (2 * ITEM + F32)
             + slots * 2 * d["n"] * F32)
    return 6.0 * elems, moved


def prefill_scan(cfg, lengths):
    """(operations, bytes) of ONE layer's selective scan over prompts of
    ``lengths`` REAL tokens (padding is not work): x read and y written in
    bfloat16, Delta, B, C read in float32, each prompt's final state
    written once; the state itself stays on the chip."""
    d = _d(cfg)
    tokens = float(sum(lengths))
    moved = (tokens * (d["d"] * (2 * ITEM + F32) + 2 * d["n"] * F32)
             + len(lengths) * d["n"] * d["d"] * F32)
    return 6.0 * tokens * d["n"] * d["d"], moved


def causal_conv(cfg, tokens):
    """(operations, bytes) of ONE layer's depthwise convolution over
    ``tokens`` rows: each read and written once."""
    d = _d(cfg)
    return 2.0 * tokens * d["kw"] * d["d"], 2.0 * tokens * d["d"] * ITEM


def gqa_decode_attention(cfg, rows, queries):
    """(operations, bytes) of ONE layer's grouped-query decode attention:
    ``queries`` slots over ``rows`` cached positions IN TOTAL. Each
    position's K row and V row is read once for the whole group of query
    heads; queries read and outputs written once."""
    d = _d(cfg)
    ops = 4.0 * d["H"] * d["dh"] * rows
    moved = (2 * rows * d["Hkv"] * d["dh"]
             + 2 * queries * d["H"] * d["dh"]) * ITEM
    return ops, moved


def least_seconds(ops, moved, peaks):
    """The roofline: the larger of operations over the peak rate and
    bytes over the peak bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
