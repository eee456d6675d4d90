"""Operations and bytes that the ALGORITHM of each kernel of the
delta-rule linear-attention / NoPE latent-attention decoder with a leading
dense layer and a held shard of routed experts needs (``kimi_linear_5l``),
from its shapes and the configuration's own keys: the least the
mathematics asks for (a live slot's matrix state read once and written
once a token, each resident latent row once for all heads, an expert that
got a token read once a call), so a share of a roofline cannot pass 100%.
No kernel is new: the delta rule's, the convolution's and the grouped
products' counts are ``kernel_costs_solar.py``'s AS THEY ARE, called with
this configuration under that family's names (``solar_view``); the latent
layer's are the absorbed decode's and the expanded prefill's of
``kernel_costs_longcat.py``, written at this configuration's keys (no
compressed query, one latent layer). A multiply-add is two operations;
parameters, latent rows, the convolution's window and activations are
bfloat16 (2 bytes), the matrix state, the log decay, beta and the mixer's
output before its norm float32 (4). A share is ``least seconds / measured
seconds``.
"""

from perfbench import kernel_costs_solar as solar

ITEM, F32, CHUNK = solar.ITEM, solar.F32, solar.CHUNK
least_seconds = solar.least_seconds


def latent_layers(cfg):
    """The latent layers, counted from 0."""
    return [i - 1 for i in cfg["linear_attn_config"]["full_attn_layers"]]


def solar_view(cfg):
    """This configuration under ``solar_open2``'s names for what the two
    families share (the linear mixer, the experts, the vocabulary): what
    ``kernel_costs_solar``'s functions read. Its grouped-query functions
    are never called with it."""
    return dict(cfg, gqa_layers=latent_layers(cfg),
                n_routed_experts=cfg["num_experts"],
                num_experts_per_tok=cfg["num_experts_per_token"],
                n_shared_experts=cfg["num_shared_experts"])


def _d(cfg):
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], C=cfg["kv_lora_rank"],
        F=cfg["intermediate_size"], dense=cfg["first_k_dense_replace"],
        L=cfg["num_hidden_layers"], latent=len(latent_layers(cfg)))


def expert_parameters(cfg):
    """One routed expert (gate, up, down)."""
    return solar.expert_parameters(cfg)


def parameter_count(cfg):
    """The configuration AS IT IS RUN: its own depth, the experts HELD,
    the router's every output, the vocabulary's slice."""
    d = _d(cfg)
    D, H = d["D"], d["H"]
    shared = solar.parameter_count(solar_view(cfg))
    latent = (D * H * (d["dn"] + d["dr"])          # q, straight from u
              + D * (d["C"] + d["dr"]) + d["C"]    # kv_a and its norm
              + d["C"] * H * (d["dn"] + d["dv"])   # kv_b
              + H * d["dv"] * D)                   # o
    dense = 3 * D * d["F"]
    moe = (shared["shared_expert"] + shared["router"]
           + shared["held_experts"])
    n_moe = d["L"] - d["dense"]
    mixers = ((d["L"] - d["latent"]) * shared["linear_mixer"]
              + d["latent"] * latent)
    return {"linear_mixer": shared["linear_mixer"], "latent_mixer": latent,
            "dense_ffn": dense, "shared_expert": shared["shared_expert"],
            "router": shared["router"],
            "routed_expert": shared["routed_expert"],
            "held_experts": shared["held_experts"], "expert_layers": n_moe,
            "embedding": shared["embedding"], "head": shared["head"],
            "total": (2 * shared["embedding"] + D + mixers
                      + d["L"] * 2 * D + d["dense"] * dense + n_moe * moe)}


def state_bytes_per_slot(cfg):
    """A slot's fixed-size state: per linear layer the float32 ``S``
    [heads, dk, dv] and the convolution's bfloat16 window."""
    return solar.state_bytes_per_slot(solar_view(cfg))


def latent_row_bytes(cfg):
    """One position's row in ONE latent layer as the algorithm reads it:
    ``kv_lora_rank + qk_rope_head_dim`` wide (the pool holds it in 640
    lanes: ``geometry["latent_row_bytes"]``)."""
    d = _d(cfg)
    return (d["C"] + d["dr"]) * ITEM


def decode_step_bytes(cfg, live_slots, live_rows, experts_hit,
                      row_bytes=None):
    """Bytes ONE decode token step must move: every parameter outside the
    routed experts but the embedding table once, the weights of the
    ``experts_hit`` held experts an expert layer that got a token, the
    LIVE slots' state and window read once and written once, the live
    latent rows once a latent layer (``row_bytes`` a row: the algorithm's
    when None)."""
    return sum(decode_step_parts(cfg, live_slots, live_rows, experts_hit,
                                 row_bytes).values())


def decode_step_parts(cfg, live_slots, live_rows, experts_hit,
                      row_bytes=None):
    """``decode_step_bytes`` by part: ``experts``, ``weights`` (the
    others), ``state``, ``latent``."""
    d = _d(cfg)
    count = parameter_count(cfg)
    n_moe = count["expert_layers"]
    fixed = (count["total"] - count["embedding"]
             - n_moe * count["held_experts"])
    rows = latent_row_bytes(cfg) if row_bytes is None else row_bytes
    return {"weights": fixed * ITEM,
            "experts": n_moe * experts_hit * count["routed_expert"] * ITEM,
            "state": 2 * live_slots * state_bytes_per_slot(cfg),
            "latent": live_rows * d["latent"] * rows}


def state_update(cfg, slots):
    """(operations, bytes) of ONE layer's one-token delta-rule update of
    ``slots`` slots (``kernel_costs_solar.state_update``)."""
    return solar.state_update(solar_view(cfg), slots)


def chunk_prefill(cfg, lengths):
    """(operations, bytes) of ONE layer's chunked delta rule over prompts
    of ``lengths`` REAL tokens (``kernel_costs_solar.chunk_prefill``)."""
    return solar.chunk_prefill(solar_view(cfg), lengths)


def expert_matmuls(cfg, held_pairs, experts_hit):
    """(operations, bytes) of ONE layer's three grouped products
    (``kernel_costs_solar.expert_matmuls``)."""
    return solar.expert_matmuls(solar_view(cfg), held_pairs, experts_hit)


def latent_decode_attention(cfg, rows, queries):
    """(operations, bytes) of ONE latent layer's absorbed-form decode
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
    """(operations, bytes) of ONE latent layer's causal prefill attention
    over prompts of ``lengths`` (expanded form: queries and keys ``dn +
    dr`` wide, values ``dv``): the lower triangle's two products at their
    OWN widths, q, k, v read and the output written once."""
    d = _d(cfg)
    dq = d["dn"] + d["dr"]
    pairs = sum(n * (n + 1) / 2.0 for n in lengths)
    ops = 2.0 * d["H"] * pairs * (dq + d["dv"])
    moved = sum(lengths) * d["H"] * (2 * dq + 2 * d["dv"]) * ITEM
    return ops, moved


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "kimi_linear_5l.json")) as f:
        cfg = json.load(f)
    for key, value in parameter_count(cfg).items():
        print("%-16s %8.1f M" % (key, value / 1e6))
    S = cfg["pool"]["num_slots"]
    print("state a slot %.2f MB, a latent row %d B"
          % (state_bytes_per_slot(cfg) / 1e6, latent_row_bytes(cfg)))
    for rows in (1024, 1536, 2048):
        parts = decode_step_parts(cfg, S, S * rows, cfg["num_experts"])
        whole = sum(parts.values())
        print("a step of %d slots at %d rows each: %.2f GB: %s" % (
            S, rows, whole / 1e9, ", ".join(
                "%s %.2f (%.0f%%)" % (k, v / 1e9, 100.0 * v / whole)
                for k, v in parts.items())))
