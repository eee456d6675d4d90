"""Layers of a decoder-only block with full and sliding-window attention
layers (``ops/window_ops.py``): the per-head query/key norms with RoPE,
the output gate, and a window layer's ring of cache pages. Like
``layers/decoder.py`` they take flat token rows ``[N, ...]`` and the
parameters as variables that the serving builder
(``models/windowed_moe_decoder.py``) declares by name."""

from paddle_tpu.layer_helper import LayerHelper

__all__ = [
    "qk_norm_rope",
    "sigmoid_gate",
    "window_row_write",
    "window_row_prefill",
    "window_prefill_attention",
    "window_paged_attention",
]


def qk_norm_rope(q, k, q_norm, k_norm, heads, kv_heads, rope=True,
                 theta=10000.0, positions=None, period=0, epsilon=1e-5,
                 name=None):
    """RMSNorm over each head of ``q`` [N, heads * dh] and ``k`` [N,
    kv_heads * dh], then RoPE on both where ``rope``: at ``positions``
    [N] (decode), else at ``n % period`` (a prefill dispatch's bucket)."""
    helper = LayerHelper("qk_norm_rope", name=name)
    q_out = helper.create_variable_for_type_inference(q.dtype)
    k_out = helper.create_variable_for_type_inference(k.dtype)
    inputs = {"Q": [q], "K": [k], "QNorm": [q_norm], "KNorm": [k_norm]}
    if positions is not None:
        inputs["Positions"] = [positions]
    helper.append_op(
        type="qk_norm_rope", inputs=inputs,
        outputs={"QOut": [q_out], "KOut": [k_out]},
        attrs={"heads": int(heads), "kv_heads": int(kv_heads),
               "rope": bool(rope), "theta": float(theta),
               "period": int(period), "epsilon": float(epsilon)})
    return q_out, k_out


def sigmoid_gate(x, gate, name=None):
    """``x * sigmoid(gate)``."""
    helper = LayerHelper("sigmoid_gate", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="sigmoid_gate", inputs={"X": [x], "Gate": [gate]},
                     outputs={"Out": [out]})
    return out


def window_row_write(pool, rows, ring_table, pos, name=None):
    """Decode's write of one row a slot into a window layer's ring of
    pages (column ``(pos // page_size) % R`` of ``ring_table``), in
    place."""
    helper = LayerHelper("window_row_write", name=name)
    helper.append_op(
        type="window_row_write",
        inputs={"Pool": [pool], "Rows": [rows], "PageTable": [ring_table],
                "Pos": [pos]},
        outputs={"PoolOut": [pool]})
    return pool


def window_row_prefill(pool, rows, ring_rows, lens, window, name=None):
    """Prefill's write into a window layer's ring, in place: only the
    pages the window still needs after each prompt."""
    helper = LayerHelper("window_row_prefill", name=name)
    helper.append_op(
        type="window_row_prefill",
        inputs={"Pool": [pool], "Rows": [rows], "PageRows": [ring_rows],
                "Lens": [lens]},
        outputs={"PoolOut": [pool]}, attrs={"window": int(window)})
    return pool


def window_prefill_attention(q, k, v, prompts, heads, kv_heads, window=0,
                             name=None):
    """Causal grouped-query attention of ``prompts`` prompts of equal
    (bucket) length through the flash kernel at tiles sized for long
    buckets; with ``window`` > 0 a query sees its last ``window``
    positions only: ``[N, heads * dh]``."""
    helper = LayerHelper("window_prefill_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        type="window_prefill_attention",
        inputs={"Q": [q], "K": [k], "V": [v]}, outputs={"Out": [out]},
        attrs={"prompts": int(prompts), "heads": int(heads),
               "kv_heads": int(kv_heads), "window": int(window)})
    return out


def window_paged_attention(q, k_pool, v_pool, ring_table, lengths, heads,
                           window, name=None):
    """Grouped-query decode attention of every slot over the last
    ``window`` of its rows (``kernels/window_paged_attention.py``):
    ``[S, heads * dh]``."""
    helper = LayerHelper("window_paged_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        type="window_paged_attention",
        inputs={"Q": [q], "KPool": [k_pool], "VPool": [v_pool],
                "PageTable": [ring_table], "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"heads": int(heads), "window": int(window)})
    return out
