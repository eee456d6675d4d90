"""Operations and bytes that each named kernel's ALGORITHM needs, from its
shapes, and the roofline share they give against the chip's peaks
(``peaks.json``). Recomputed or padded work does not count: the numbers
are what the mathematics asks for, so a share cannot pass 100%.

A multiply-add is two operations. Every matrix product on the v5e's MXU
is priced at the bf16 peak: float32 operands at the default precision run
as one bf16 pass.
"""


def flash_attention_fwd(batch, heads, q_len, kv_len, head_dim, itemsize,
                        causal=False):
    """(operations, bytes) of softmax(QK^T)V for ``batch x heads`` heads.
    Causal attention needs only the lower triangle."""
    pairs = q_len * (kv_len + 1) / 2.0 if causal else q_len * kv_len
    ops = 4.0 * batch * heads * pairs * head_dim
    moved = batch * heads * head_dim * itemsize * (2 * q_len + 2 * kv_len)
    return ops, moved


def decode_attention(context_tokens, queries, heads, head_dim, itemsize):
    """(operations, bytes) of single-query attention, ``queries`` queries
    over ``context_tokens`` keys and values IN TOTAL (the sum of the
    queries' own context lengths): the paged self-attention of a decode
    step and its cross attention at query length 1 alike. Each key and
    value is read once; q and the output are read and written once."""
    ops = 4.0 * heads * head_dim * context_tokens
    moved = heads * head_dim * itemsize * (2.0 * context_tokens
                                           + 2.0 * queries)
    return ops, moved


def transformer_train_step(cfg, batch):
    """Operations of ONE optimizer step's forward and backward passes over
    ``batch`` full sequences: 6 x the non-embedding parameters each token
    passes (2 forward, 4 backward), the output projection, and attention
    (scores and context, causal halved in the decoder's self-attention).
    Embedding lookups, norms, softmax and the optimizer are not counted,
    and nothing recomputed is."""
    L, D, F = cfg["n_layer"], cfg["d_model"], cfg["d_inner"]
    S = T = cfg["max_length"]
    V = cfg["trg_vocab_size"]
    enc_params = L * (4 * D * D + 2 * D * F)
    dec_params = L * (8 * D * D + 2 * D * F) + D * V
    dense = 2.0 * batch * (enc_params * S + dec_params * T)
    attn = batch * L * (4.0 * S * S * D        # encoder self
                        + 2.0 * T * (T + 1) * D  # decoder self, causal
                        + 4.0 * T * S * D)     # cross
    return 3.0 * (dense + attn)


def roofline_share(ops, moved, seconds, peaks):
    """(share in %, which bound: "compute" or "memory")."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = moved / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
