"""Operations and bytes that the ALGORITHM of each kernel of the dense
Gated DeltaNet / multi-head attention decoder needs (``olmo_hybrid_8l``),
from its shapes and the configuration's own keys: the least the
mathematics asks for (a live slot's matrix state read once and written
once a token at its PUBLISHED size, ``heads x dk x dv`` float32, whatever
the array's layout pads it to, so a padded layout reads as a lower share;
each resident K and V row once; every weight once), so a share of a
roofline cannot pass 100%. The delta rule is ``kernels/delta_rule.py``'s
with keys of 96 beside values of 192 and ONE log decay a head (not a key
channel: ``kernel_costs_solar.py`` counts that form); the full layer's
decode is the grouped-query kernel at a group of one. A multiply-add is two
operations; parameters, K/V rows, the convolution's window and activations
are bfloat16 (2 bytes), the matrix state, the log decay, beta and the
mixer's output before its norm float32 (4). A share is ``least seconds /
measured seconds``.
"""

from perfbench.kernel_costs_jamba import F32, ITEM, least_seconds  # noqa: F401

CHUNK = 64        # kernels/delta_rule.py CHUNK: tokens a chunk


def _d(cfg):
    kinds = cfg["layer_types"]
    full = sum(k == "full_attention" for k in kinds)
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        D=D, H=H, dh=D // H, Hl=cfg["linear_num_key_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        kw=cfg["linear_conv_kernel_dim"], F=cfg["intermediate_size"],
        L=len(kinds), V=cfg["vocab_size"], full=full,
        linear=len(kinds) - full)


def parameter_count(cfg):
    """The configuration AS IT IS RUN (its own depth, the whole
    vocabulary), by part."""
    d = _d(cfg)
    D, Hl, dk, dv = d["D"], d["Hl"], d["dk"], d["dv"]
    lw = Hl * (2 * dk + dv)                     # the q | k | v row
    linear = (D * lw + d["kw"] * lw             # qkv and its convolution
              + 2 * D * Hl + 2 * Hl             # a, beta; dt_bias, a_log
              + D * Hl * dv + dv                # the gate; the head norm
              + Hl * dv * D)                    # o
    row = d["H"] * d["dh"]
    full = 4 * D * row + 2 * row                # q, k, v, o; q and k norms
    ffn = 3 * D * d["F"]
    norms = 2 * D                               # a block's two output norms
    emb = d["V"] * D
    return {"linear_mixer": linear, "full_mixer": full, "ffn": ffn,
            "linear_layer": linear + ffn + norms,
            "full_layer": full + ffn + norms,
            "embedding": emb, "head": emb,
            "total": (2 * emb + D + d["linear"] * (linear + ffn + norms)
                      + d["full"] * (full + ffn + norms))}


def state_bytes_per_slot_layer(cfg):
    """A slot's matrix state in ONE linear layer at its published size:
    float32 ``[heads, dk, dv]``."""
    d = _d(cfg)
    return d["Hl"] * d["dk"] * d["dv"] * F32


def window_bytes_per_slot_layer(cfg):
    """A slot's convolution window in ONE linear layer: bfloat16 ``[taps -
    1, q | k | v]``."""
    d = _d(cfg)
    return (d["kw"] - 1) * d["Hl"] * (2 * d["dk"] + d["dv"]) * ITEM


def kv_row_bytes(cfg):
    """One position's K row and V row in ONE full layer."""
    d = _d(cfg)
    return 2 * d["H"] * d["dh"] * ITEM


def decode_step_parts(cfg, live_slots, live_rows, state_bytes=None,
                      row_bytes=None):
    """Bytes ONE decode token step must move, by part: ``weights`` (every
    parameter but the embedding table, once), ``state`` (the live slots'
    matrix state and window read once and written once, every linear
    layer; ``state_bytes`` a slot a layer as the ARRAY holds the state,
    the published size when None), ``rows`` (the live K and V rows once a
    full layer; ``row_bytes`` a position a layer)."""
    d = _d(cfg)
    count = parameter_count(cfg)
    state = state_bytes_per_slot_layer(cfg) if state_bytes is None \
        else state_bytes
    rows = kv_row_bytes(cfg) if row_bytes is None else row_bytes
    return {"weights": (count["total"] - count["embedding"]) * ITEM,
            "state": 2 * live_slots * d["linear"]
            * (state + window_bytes_per_slot_layer(cfg)),
            "rows": live_rows * d["full"] * rows}


def decode_step_bytes(cfg, live_slots, live_rows):
    return sum(decode_step_parts(cfg, live_slots, live_rows).values())


def state_update(cfg, slots):
    """(operations, bytes) of ONE layer's one-token delta-rule update of
    ``slots`` slots: ``S`` read and written once; q, k, v read in
    bfloat16, the log decay (a head) and beta in float32, the output
    written in float32; a product and three multiply-adds a state element
    (the decay, ``S'^T k``, the rank-one correction, ``S^T q``)."""
    d = _d(cfg)
    Hl, dk, dv = d["Hl"], d["dk"], d["dv"]
    elems = slots * Hl * dk * dv
    moved = (2 * elems * F32 + slots * Hl * (2 * dk + dv) * ITEM
             + slots * Hl * (2 + dv) * F32)
    return 7.0 * elems, moved


def chunk_prefill(cfg, lengths):
    """(operations, bytes) of ONE layer's chunked delta rule over prompts
    of ``lengths`` REAL tokens (padding is not work), a decay a head. A
    chunk of C tokens and a head: ``K S_0``, ``Q S_0`` and the state's
    update are 2 C dk dv each, the lower triangles of ``K K^T`` and ``Q
    K^T`` C^2 dk each, the solve and ``R W`` C^2 dv each. q, k, v read in
    bfloat16, the log decay and beta (a head each) in float32, the output
    written in float32 and each prompt's final state once; the state
    itself stays on the chip."""
    d = _d(cfg)
    Hl, dk, dv = d["Hl"], d["dk"], d["dv"]
    tokens = float(sum(lengths))
    ops = tokens * Hl * (6.0 * dk * dv + 2.0 * CHUNK * (dk + dv))
    moved = (tokens * Hl * ((2 * dk + dv) * ITEM + 2 * F32 + dv * F32)
             + len(lengths) * Hl * dk * dv * F32)
    return ops, moved


def causal_conv(cfg, tokens):
    """(operations, bytes) of ONE layer's depthwise convolution over
    ``tokens`` rows of ``q | k | v``: each read and written once."""
    d = _d(cfg)
    width = d["Hl"] * (2 * d["dk"] + d["dv"])
    return 2.0 * tokens * d["kw"] * width, 2.0 * tokens * width * ITEM


def mha_decode_attention(cfg, rows, queries):
    """(operations, bytes) of ONE full layer's multi-head decode
    attention: ``queries`` slots over ``rows`` cached positions IN TOTAL.
    Each position's K row and V row (every head's lanes) is read once;
    queries read and outputs written once."""
    d = _d(cfg)
    ops = 4.0 * d["H"] * d["dh"] * rows
    moved = (2 * rows + 2 * queries) * d["H"] * d["dh"] * ITEM
    return ops, moved


def prefill_attention(cfg, lengths):
    """(operations, bytes) of ONE full layer's causal prefill attention
    over prompts of ``lengths``: the lower triangle's two products; q, k,
    v read and the output written once a head."""
    d = _d(cfg)
    pairs = sum(n * (n + 1) / 2.0 for n in lengths)
    ops = 4.0 * d["H"] * pairs * d["dh"]
    moved = sum(lengths) * 4 * d["H"] * d["dh"] * ITEM
    return ops, moved


if __name__ == "__main__":
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "olmo_hybrid_8l.json")) as f:
        cfg = json.load(f)
    for key, value in parameter_count(cfg).items():
        print("%-16s %8.1f M" % (key, value / 1e6))
    S = cfg["pool"]["num_slots"]
    print("state a slot a layer %.3f MB, window %d B, K/V a row a layer %d B"
          % (state_bytes_per_slot_layer(cfg) / 1e6,
             window_bytes_per_slot_layer(cfg), kv_row_bytes(cfg)))
    for rows in (600, 850, 1100):
        parts = decode_step_parts(cfg, S, S * rows)
        whole = sum(parts.values())
        print("a step of %d slots at %d rows each: %.2f GB: %s" % (
            S, rows, whole / 1e9, ", ".join(
                "%s %.2f (%.0f%%)" % (k, v / 1e9, 100.0 * v / whole)
                for k, v in parts.items())))
