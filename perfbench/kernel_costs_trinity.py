"""Operations and bytes that the ALGORITHM of each kernel of the
window/full-attention, routed-expert decoder needs, from its shapes and
the configuration's own keys: the least the mathematics asks for (every
expert that can have got a row read once a call, each VISIBLE K/V row
once: a window layer's query sees its last ``sliding_window`` positions
and no more), so a share of a roofline cannot pass 100%. A multiply-add is
two operations; parameters, rows and activations are bfloat16 (2 bytes).
``kernel_costs.py``, ``kernel_costs_glm.py`` and ``kernel_costs_jamba.py``
hold the other families'; a share is ``least seconds / measured seconds``.
"""

from perfbench.kernel_costs_glm import least_seconds  # noqa: F401

ITEM = 2  # bytes of a bfloat16
SLIDING = "sliding_attention"


def _d(cfg):
    kinds = list(cfg["layer_types"])
    return dict(
        D=cfg["hidden_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
        F=cfg["intermediate_size"], Fm=cfg["moe_intermediate_size"],
        E=cfg["num_experts"], k=cfg["num_experts_per_tok"],
        shared=cfg.get("num_shared_experts", 0),
        dense=cfg.get("num_dense_layers", 0), L=cfg["num_hidden_layers"],
        V=cfg["vocab_size"], W=cfg["sliding_window"],
        window=kinds.count(SLIDING), full=len(kinds) - kinds.count(SLIDING))


def attention_parameters(cfg):
    """{"q", "k", "v", "o", "gate", "norms", "total"} of one layer's
    attention (the two per-head norms with it)."""
    d = _d(cfg)
    qw, row = d["H"] * d["dh"], d["Hkv"] * d["dh"]
    out = {"q": d["D"] * qw, "k": d["D"] * row, "v": d["D"] * row,
           "o": qw * d["D"], "gate": d["D"] * qw, "norms": 2 * d["dh"]}
    out["total"] = sum(out.values())
    return out


def expert_parameters(cfg):
    """One routed expert (gate, up, down)."""
    d = _d(cfg)
    return 3 * d["D"] * d["Fm"]


def parameter_count(cfg):
    """{"attention_a_layer", "embedding", "head", "dense_layer",
    "expert_layer", "routed_experts_a_layer", "total"} of the
    configuration AS IT IS RUN (its own ``num_hidden_layers``)."""
    d = _d(cfg)
    attn = attention_parameters(cfg)["total"]
    block = attn + 4 * d["D"]                      # with the four norms
    dense = block + 3 * d["D"] * d["F"]
    routed = d["E"] * expert_parameters(cfg)
    expert = (block + routed + d["shared"] * expert_parameters(cfg)
              + d["D"] * d["E"] + d["E"])          # router and its bias
    emb = d["V"] * d["D"]
    return {"attention_a_layer": attn, "embedding": emb,
            "head": emb, "dense_layer": dense, "expert_layer": expert,
            "routed_experts_a_layer": routed,
            "total": (2 * emb + d["D"] + d["dense"] * dense
                      + (d["L"] - d["dense"]) * expert)}


def row_bytes(cfg):
    """One K row and one V row of every key/value head, a layer."""
    d = _d(cfg)
    return 2 * d["Hkv"] * d["dh"] * ITEM


def decode_step_bytes(cfg, full_rows, window_rows):
    """Bytes ONE decode token step must read: every parameter but the
    embedding table once (the step gathers only the live tokens' rows of
    it), the live rows of every full layer and the VISIBLE rows of every
    window layer once."""
    d, count = _d(cfg), parameter_count(cfg)
    return ((count["total"] - count["embedding"]) * ITEM
            + (d["full"] * full_rows + d["window"] * window_rows)
            * row_bytes(cfg))


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


def decode_attention(cfg, rows, queries):
    """(operations, bytes) of ONE layer's grouped-query decode attention,
    window or full: ``queries`` slots over ``rows`` VISIBLE positions in
    total (a window layer's are the rows its queries can see, not the
    whole pages its ring holds). Each position's K row and V row is read
    once for the whole group of query heads; queries read and outputs
    written once."""
    d = _d(cfg)
    ops = 4.0 * d["H"] * d["dh"] * rows
    moved = rows * row_bytes(cfg) + 2 * queries * d["H"] * d["dh"] * ITEM
    return ops, moved


def visible_pairs(length, window=None):
    """(query, key) pairs of a causal prompt of ``length`` tokens; with a
    ``window`` a query sees its last ``window`` positions."""
    if window is None or length <= window:
        return length * (length + 1) / 2.0
    return window * (window + 1) / 2.0 + (length - window) * float(window)


def prefill_attention(cfg, lengths):
    """(operations, bytes) of ALL layers' causal prefill attention over
    prompts of ``lengths`` REAL tokens: the products of the visible band
    of each prompt's own length (a window layer's band is
    ``sliding_window`` wide), q read and the output written once a query
    head, k and v once a key/value head."""
    d = _d(cfg)
    pairs = sum(d["full"] * visible_pairs(n)
                + d["window"] * visible_pairs(n, d["W"]) for n in lengths)
    ops = 4.0 * d["H"] * d["dh"] * pairs
    moved = (d["L"] * sum(lengths)
             * 2 * (d["H"] + d["Hkv"]) * d["dh"] * ITEM)
    return ops, moved
