"""Operations and bytes that the ALGORITHM of each kernel of the
delta-rule linear-attention / grouped-query decoder with a held shard of
routed experts needs (``solar_open2_4l``), from its shapes and the
configuration's own keys: the least the mathematics asks for (a live
slot's matrix state read once and written once a token, each real token's
rows once, an expert that got a token read once a call), so a share of a
roofline cannot pass 100%. A multiply-add is two operations; parameters,
K/V rows, the convolution's window and activations are bfloat16 (2 bytes),
the matrix state, the log decay, beta and the mixer's output before its
norm float32 (4). A share is ``least seconds / measured seconds``.
"""

from perfbench.kernel_costs_jamba import F32, ITEM, least_seconds  # noqa: F401

CHUNK = 64        # kernels/delta_rule.py CHUNK: tokens a chunk


def _d(cfg):
    lin = cfg["linear_attn_config"]
    gqa = len(cfg["gqa_layers"])
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
        Hl=lin["num_heads"], dl=lin["head_dim"],
        kw=lin["short_conv_kernel_size"],
        Fm=cfg["moe_intermediate_size"], E=cfg["n_routed_experts"],
        Er=cfg["expert_shard"]["of"], k=cfg["num_experts_per_tok"],
        shared=cfg.get("n_shared_experts", 0),
        L=cfg["num_hidden_layers"], V=cfg["vocab_size"], gqa=gqa,
        linear=cfg["num_hidden_layers"] - gqa)


def expert_parameters(cfg):
    """One routed expert (gate, up, down)."""
    d = _d(cfg)
    return 3 * d["D"] * d["Fm"]


def parameter_count(cfg):
    """The configuration AS IT IS RUN: its own depth, the experts HELD,
    the router's every output, the vocabulary's slice."""
    d = _d(cfg)
    D, lw, dl = d["D"], d["Hl"] * d["dl"], d["dl"]
    linear = (4 * D * lw                        # q, k, v, o
              + 2 * (D * dl + dl * lw)          # the decay and output gates
              + D * d["Hl"]                     # beta
              + 3 * d["kw"] * lw                # the three convolutions
              + d["Hl"] + lw + lw + dl)         # A_log, dt_bias, b_g, o_norm
    qw, row = d["H"] * d["dh"], d["Hkv"] * d["dh"]
    gqa = 3 * D * qw + 2 * D * row              # q, gate, o; k, v
    router = D * d["Er"] + d["Er"]
    shared = d["shared"] * expert_parameters(cfg)
    held = d["E"] * expert_parameters(cfg)
    outside = shared + router + 2 * D           # + the two block norms
    emb = d["V"] * D
    return {"linear_mixer": linear, "gqa_mixer": gqa,
            "shared_expert": shared, "router": router,
            "routed_expert": expert_parameters(cfg), "held_experts": held,
            "linear_layer": linear + outside + held,
            "gqa_layer": gqa + outside + held,
            "embedding": emb, "head": emb,
            "total": (2 * emb + D + d["linear"] * (linear + outside + held)
                      + d["gqa"] * (gqa + outside + held))}


def state_bytes_per_slot(cfg):
    """A slot's fixed-size state: per linear layer the float32 ``S``
    [heads, dk, dv] and the convolution's bfloat16 window [taps - 1,
    q | k | v]."""
    d = _d(cfg)
    return d["linear"] * (d["Hl"] * d["dl"] * d["dl"] * F32
                          + (d["kw"] - 1) * 3 * d["Hl"] * d["dl"] * ITEM)


def cached_bytes_per_token(cfg):
    """One K and one V row of every key/value head an attention layer."""
    d = _d(cfg)
    return d["gqa"] * 2 * d["Hkv"] * d["dh"] * ITEM


def decode_step_bytes(cfg, live_slots, live_rows, experts_hit):
    """Bytes ONE decode token step must move: every parameter outside the
    routed experts but the embedding table once, the weights of the
    ``experts_hit`` held experts a layer that got a token, the LIVE slots'
    state and window read once and written once, the live K/V rows once."""
    d = _d(cfg)
    count = parameter_count(cfg)
    fixed = (count["total"] - count["embedding"]
             - d["L"] * count["held_experts"])
    return ((fixed + d["L"] * experts_hit * count["routed_expert"]) * ITEM
            + 2 * live_slots * state_bytes_per_slot(cfg)
            + live_rows * cached_bytes_per_token(cfg))


def state_update(cfg, slots):
    """(operations, bytes) of ONE layer's one-token delta-rule update of
    ``slots`` slots: ``S`` read and written once; q, k, v read in
    bfloat16, the log decay and beta in float32, the output written in
    float32; a product and three multiply-adds a state element (the
    decay, ``S'^T k``, the rank-one correction, ``S^T q``)."""
    d = _d(cfg)
    elems = slots * d["Hl"] * d["dl"] * d["dl"]
    rows = slots * d["Hl"] * d["dl"]
    moved = 2 * elems * F32 + rows * (3 * ITEM + 2 * F32) \
        + slots * d["Hl"] * F32
    return 7.0 * elems, moved


def chunk_prefill(cfg, lengths):
    """(operations, bytes) of ONE layer's chunked delta rule over prompts
    of ``lengths`` REAL tokens (padding is not work). A chunk of C tokens
    and a head: ``(K exp G) S_0``, ``(Q exp G) S_0`` and the state's
    update are 2 C dk dv each, the lower triangles of ``P`` and ``R`` C^2
    dk each, the solve and ``R W`` C^2 dv each. q, k, v read in bfloat16,
    the log decay and beta in float32, the output written in float32 and
    each prompt's final state once; the state itself stays on the chip."""
    d = _d(cfg)
    tokens = float(sum(lengths))
    dk = dv = d["dl"]
    ops = tokens * d["Hl"] * (6.0 * dk * dv + 2.0 * CHUNK * (dk + dv))
    moved = (tokens * d["Hl"] * (2 * dk * ITEM + dv * ITEM + dk * F32 + F32
                                 + dv * F32)
             + len(lengths) * d["Hl"] * dk * dv * F32)
    return ops, moved


def causal_conv(cfg, tokens):
    """(operations, bytes) of ONE layer's depthwise convolution over
    ``tokens`` rows of ``q | k | v``: each read and written once."""
    d = _d(cfg)
    width = 3 * d["Hl"] * d["dl"]
    return 2.0 * tokens * d["kw"] * width, 2.0 * tokens * width * ITEM


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


def prefill_attention(cfg, lengths):
    """(operations, bytes) of ONE layer's causal prefill attention over
    prompts of ``lengths``: the lower triangle's products; q read and the
    output written once a query head, k and v once a key/value head."""
    d = _d(cfg)
    pairs = sum(n * (n + 1) / 2.0 for n in lengths)
    ops = 4.0 * d["H"] * pairs * d["dh"]
    moved = sum(lengths) * (2 * d["H"] + 2 * d["Hkv"]) * d["dh"] * ITEM
    return ops, moved


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
    with open(os.path.join(here, "configs", "solar_open2_4l.json")) as f:
        cfg = json.load(f)
    for key, value in parameter_count(cfg).items():
        print("%-16s %8.1f M parameters" % (key, value / 1e6))
    print("state a slot %.2f MB, K/V a token %d B"
          % (state_bytes_per_slot(cfg) / 1e6, cached_bytes_per_token(cfg)))
