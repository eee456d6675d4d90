"""Operations and bytes that the ALGORITHM of each kernel of the hybrid
Mamba-2 / grouped-query decoder with a held shard of routed experts needs
(``granite4_h_small_10l``), from its shapes and the configuration's own
keys: the least the mathematics asks for (a live slot's matrix state read
once and written once a token, each real token's rows once, the chunked
form's products at the real tokens and under the diagonal only, an expert
that got a token read once a call), so a share of a roofline cannot pass
100% whatever implements it. A multiply-add is two operations; parameters,
K/V rows, the convolution's window and activations are bfloat16 (2 bytes),
the matrix state, Delta and the mixer's output before its norm float32
(4). A share is ``least seconds / measured seconds``.
"""

from perfbench.kernel_costs_jamba import F32, ITEM, least_seconds  # noqa: F401

CHUNK = 256       # the published mamba_chunk_size (kernels/ssd.py CHUNK)


def _d(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    att = list(cfg["layer_types"]).count("attention")
    d = dict(
        D=D, H=H, Hkv=cfg["num_key_value_heads"],
        dh=cfg.get("head_dim") or D // H, Hm=cfg["mamba_n_heads"],
        P=cfg["mamba_d_head"], n=cfg["mamba_d_state"],
        kw=cfg["mamba_d_conv"], F=cfg["intermediate_size"],
        Fs=cfg["shared_intermediate_size"], E=cfg["num_local_experts"],
        Er=cfg["expert_shard"]["of"], k=cfg["num_experts_per_tok"],
        L=cfg["num_hidden_layers"], V=cfg["vocab_size"], att=att,
        mamba=cfg["num_hidden_layers"] - att)
    d["di"] = d["Hm"] * d["P"]
    d["cw"] = d["di"] + 2 * d["n"]          # the x | B | C row
    return d


def expert_parameters(cfg):
    """One routed expert (gate, up, down)."""
    d = _d(cfg)
    return 3 * d["D"] * d["F"]


def parameter_count(cfg):
    """The configuration AS IT IS RUN: its own depth, the experts HELD,
    the router's every output, the vocabulary's slice (one table: the
    embedding is the head)."""
    d = _d(cfg)
    D, di, cw = d["D"], d["di"], d["cw"]
    mamba = (D * di + D * cw + D * d["Hm"]  # in_proj: z, x | B | C, dt
             + d["kw"] * cw + cw            # the convolution and its bias
             + 3 * d["Hm"]                  # A_log, dt_bias, D
             + di + di * D)                 # the gated norm, out_proj
    qw, row = d["H"] * d["dh"], d["Hkv"] * d["dh"]
    att = 2 * D * qw + 2 * D * row          # q, o; k, v
    router = D * d["Er"]
    shared = 3 * D * d["Fs"]
    held = d["E"] * expert_parameters(cfg)
    outside = shared + router + 2 * D       # + the two block norms
    emb = d["V"] * D
    return {"mamba_mixer": mamba, "attention_mixer": att,
            "shared_expert": shared, "router": router,
            "routed_expert": expert_parameters(cfg), "held_experts": held,
            "mamba_layer": mamba + outside + held,
            "attention_layer": att + outside + held, "embedding": emb,
            "total": (emb + D + d["mamba"] * (mamba + outside + held)
                      + d["att"] * (att + outside + held))}


def state_bytes_per_slot(cfg):
    """A slot's fixed-size state: per Mamba-2 layer the float32 ``s``
    [heads, d_head, d_state] and the convolution's bfloat16 window
    [taps - 1, x | B | C]."""
    d = _d(cfg)
    return d["mamba"] * (d["Hm"] * d["P"] * d["n"] * F32
                         + (d["kw"] - 1) * d["cw"] * ITEM)


def cached_bytes_per_token(cfg):
    """One K and one V row of every key/value head an attention layer."""
    d = _d(cfg)
    return d["att"] * 2 * d["Hkv"] * d["dh"] * ITEM


def decode_step_bytes(cfg, live_slots, live_rows, experts_hit):
    """Bytes ONE decode token step must move: every parameter outside the
    routed experts once (the tied table is the head's operand), the
    weights of the ``experts_hit`` held experts a layer that got a token,
    the LIVE slots' state and window read once and written once, the live
    K/V rows once."""
    d = _d(cfg)
    count = parameter_count(cfg)
    fixed = count["total"] - d["L"] * count["held_experts"]
    return ((fixed + d["L"] * experts_hit * count["routed_expert"]) * ITEM
            + 2 * live_slots * state_bytes_per_slot(cfg)
            + live_rows * cached_bytes_per_token(cfg))


def state_update(cfg, slots):
    """(operations, bytes) of ONE layer's one-token Mamba-2 update of
    ``slots`` slots: ``s`` read and written once; x, B and C read in
    bfloat16, Delta in float32, the output written in float32; a product
    and two multiply-adds a state element (the decay, ``Delta x (x) B``,
    ``s C``)."""
    d = _d(cfg)
    elems = slots * d["Hm"] * d["P"] * d["n"]
    moved = 2 * elems * F32 + slots * (
        d["cw"] * ITEM + d["Hm"] * F32 + d["di"] * F32)
    return 5.0 * elems, moved


def chunk_prefill(cfg, lengths):
    """(operations, bytes) of ONE layer's chunked Mamba-2 recurrence over
    prompts of ``lengths`` REAL tokens (padding is not work), in chunks of
    the published 256. A pair of tokens ``r <= t`` of one chunk costs ``C_t
    . B_r`` once for all heads (2 N) and a head's ``[Q, Q] x [Q, P]`` term
    (2 P); a token costs a head ``C_t . s_0`` and its part of the state's
    update (2 P N each). x, B and C read in bfloat16, Delta in float32,
    the output written in float32 and each prompt's final state once; the
    state itself stays on the chip."""
    d = _d(cfg)
    tokens = float(sum(lengths))
    pairs = sum((n // CHUNK) * CHUNK * (CHUNK + 1) / 2.0
                + (n % CHUNK) * (n % CHUNK + 1) / 2.0 for n in lengths)
    ops = (pairs * (2.0 * d["n"] + d["Hm"] * 2.0 * d["P"])
           + tokens * d["Hm"] * 4.0 * d["P"] * d["n"])
    moved = (tokens * (d["cw"] * ITEM + d["Hm"] * F32 + d["di"] * F32)
             + len(lengths) * d["Hm"] * d["P"] * d["n"] * F32)
    return ops, moved


def causal_conv(cfg, tokens):
    """(operations, bytes) of ONE layer's depthwise convolution over
    ``tokens`` rows of ``x | B | C``: each read and written once."""
    d = _d(cfg)
    return 2.0 * tokens * d["kw"] * d["cw"], 2.0 * tokens * d["cw"] * ITEM


def expert_matmuls(cfg, held_pairs, experts_hit):
    """(operations, bytes) of ONE layer's three grouped products over the
    ``held_pairs`` (token, expert) rows that fell on held experts,
    ``experts_hit`` of which got any."""
    d = _d(cfg)
    ops = 2.0 * held_pairs * expert_parameters(cfg)
    moved = (experts_hit * expert_parameters(cfg)
             + 2.0 * held_pairs * d["D"]) * ITEM
    return ops, moved


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs",
                           "granite4_h_small_10l.json")) as f:
        cfg = json.load(f)
    for key, value in parameter_count(cfg).items():
        print("%-16s %8.1f M parameters" % (key, value / 1e6))
    print("state a slot %.2f MB, K/V a token %d B"
          % (state_bytes_per_slot(cfg) / 1e6, cached_bytes_per_token(cfg)))
