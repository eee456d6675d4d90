"""Operations and bytes that the ALGORITHM of each kernel of the
latent-attention decoder under learned sparse attention needs
(``glm52_dsa_5l``), from its shapes and the configuration's own keys: the
least the mathematics asks for (an expert that got a token read once a
call, each SELECTED latent row and each narrow key once), so a share of a
roofline cannot pass 100%. A multiply-add is two operations; parameters,
rows and activations are bfloat16 (2 bytes). What the dense latent decoder
shares is ``kernel_costs_glm``'s (the attention's parameters, an expert's,
the prefill's causal attention); a share is ``least seconds / measured
seconds``.
"""

from perfbench import kernel_costs_glm as glm

ITEM = glm.ITEM
least_seconds = glm.least_seconds
expert_parameters = glm.expert_parameters


def _d(cfg):
    d = glm._d(cfg)
    kinds = list(cfg["indexer_types"])
    d.update(J=cfg["index_n_heads"], dI=cfg["index_head_dim"],
             topk=cfg["index_topk"], full=kinds.count("full"),
             Er=cfg["expert_shard"]["of"])
    return d


def indexer_parameters(cfg):
    """A ``full`` layer's indexer: its queries' and key's projections, the
    key's LayerNorm, the heads' weights."""
    d = _d(cfg)
    return (d["rq"] * d["J"] * d["dI"] + d["D"] * d["dI"] + 2 * d["dI"]
            + d["D"] * d["J"])


def parameter_count(cfg):
    """The configuration AS IT IS RUN: its own depth, the experts HELD,
    the router's every output, the vocabulary's slice."""
    d = _d(cfg)
    attn = glm.attention_parameters(cfg) + 2 * d["D"]  # + the two block norms
    dense = attn + 3 * d["D"] * d["F"]
    router = d["D"] * d["Er"] + d["Er"]
    shared = d["shared"] * expert_parameters(cfg)
    held = d["E"] * expert_parameters(cfg)
    indexer = indexer_parameters(cfg)
    n_moe = d["L"] - d["dense"]
    emb = d["V"] * d["D"]
    return {"attention": attn, "indexer": indexer, "dense_ffn": dense - attn,
            "shared_expert": shared, "router": router,
            "routed_expert": expert_parameters(cfg), "held_experts": held,
            "embedding": emb, "head": emb,
            "total": (2 * emb + d["D"] + d["dense"] * dense
                      + n_moe * (attn + shared + router + held)
                      + d["full"] * indexer)}


def expected_experts_hit(cfg, pairs):
    """Held experts that get at least one of ``pairs`` (token, expert)
    choices spread evenly over all the router's outputs."""
    d = _d(cfg)
    return d["E"] * (1.0 - (1.0 - 1.0 / d["Er"]) ** pairs)


def decode_step_bytes(cfg, selected_rows, resident_rows, experts_hit):
    """Bytes ONE decode token step must read: every parameter outside the
    routed experts but the embedding table once, the weights of the
    ``experts_hit`` held experts a layer that got a token, the selected
    latent rows of every layer and every resident narrow key of the
    ``full`` layers once."""
    d = _d(cfg)
    count = parameter_count(cfg)
    n_moe = d["L"] - d["dense"]
    fixed = (count["total"] - count["embedding"]
             - n_moe * count["held_experts"])
    return ((fixed + n_moe * experts_hit * count["routed_expert"]) * ITEM
            + selected_rows * d["L"] * (d["C"] + d["dr"]) * ITEM
            + resident_rows * d["full"] * d["dI"] * ITEM)


def index_score_decode(cfg, resident_rows, queries):
    """(operations, bytes) of ONE ``full`` layer's index scores: every
    head's product with every resident key, each key read once."""
    d = _d(cfg)
    ops = 2.0 * d["J"] * d["dI"] * resident_rows
    moved = (resident_rows * d["dI"] + queries * d["J"] * d["dI"]) * ITEM \
        + resident_rows * 4
    return ops, moved


def sparse_decode_attention(cfg, selected_rows, queries):
    """(operations, bytes) of ONE layer's absorbed-form attention over the
    selected rows: each is read once for all heads."""
    return glm.latent_decode_attention(cfg, selected_rows, queries)


def prefill_attention(cfg, lengths):
    """(operations, bytes) of ONE layer's prefill attention: row ``t`` of a
    prompt attends ``min(t + 1, index_topk)`` positions (the mask's ones;
    the kernel walks the whole lower triangle); q, k, v read and the
    output written once, the mask (a byte a pair of the triangle) read
    once a head where the bucket has one."""
    d = _d(cfg)
    dq = d["dn"] + d["dr"]
    k = d["topk"]
    pairs = sum(min(n, k) * (min(n, k) + 1) / 2.0 + max(n - k, 0) * k
                for n in lengths)
    ops = 2.0 * d["H"] * pairs * (dq + d["dv"])
    moved = sum(lengths) * d["H"] * (2 * dq + 2 * d["dv"]) * ITEM
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
    with open(os.path.join(here, "configs", "glm52_dsa_5l.json")) as f:
        cfg = json.load(f)
    for key, value in parameter_count(cfg).items():
        print("%-16s %8.1f M parameters" % (key, value / 1e6))
