"""Layers of a hybrid decoder-only block (``ops/ssm_ops.py``): the
selective state-space mixer's pieces in their prefill and one-token forms,
and grouped-query attention over paged K/V row pools. Like
``layers/decoder.py`` they take flat token rows ``[N, ...]`` and the
parameters as variables that the serving builder
(``models/hybrid_ssm_decoder.py``) declares by name."""

from paddle_tpu.layer_helper import LayerHelper

__all__ = [
    "ssm_causal_conv",
    "ssm_delta_b_c",
    "ssm_prefill_scan",
    "ssm_conv_step",
    "ssm_state_update",
    "slot_state_write",
    "gqa_prefill_attention",
    "gqa_paged_attention",
    "tied_vocab_projection",
]


def _new(helper, like, dtype=None):
    return helper.create_variable_for_type_inference(dtype or like.dtype)


def ssm_causal_conv(x, w, bias, lens, name=None):
    """The causal depthwise convolution with SiLU over a prefill
    dispatch's prompts (one a bucket row of ``x`` [N, d]). Returns (out
    [N, d], window [d_conv - 1, prompts, d]: each prompt's last real
    inputs)."""
    helper = LayerHelper("ssm_causal_conv", name=name)
    out, window = _new(helper, x), _new(helper, x)
    helper.append_op(
        type="ssm_causal_conv",
        inputs={"X": [x], "W": [w], "Bias": [bias], "Lens": [lens]},
        outputs={"Out": [out], "Window": [window]})
    return out, window


def ssm_delta_b_c(u, dt_norm, b_norm, c_norm, dt_proj, dt_bias, dt_rank,
                  d_state, epsilon=1e-6, name=None):
    """``u`` = ``x_proj(x)`` [N, dt_rank + 2 d_state] -> (Delta [N, d],
    B [N, d_state], C [N, d_state]), all float32."""
    helper = LayerHelper("ssm_delta_b_c", name=name)
    outs = [_new(helper, u, "float32") for _ in range(3)]
    helper.append_op(
        type="ssm_delta_b_c",
        inputs={"X": [u], "DtNorm": [dt_norm], "BNorm": [b_norm],
                "CNorm": [c_norm], "DtProj": [dt_proj],
                "DtBias": [dt_bias]},
        outputs={"Delta": [outs[0]], "B": [outs[1]], "C": [outs[2]]},
        attrs={"dt_rank": int(dt_rank), "d_state": int(d_state),
               "epsilon": float(epsilon)})
    return outs


def ssm_prefill_scan(x, delta, b, c, a_log, d_skip, gate, lens, name=None):
    """The selective scan over a prefill dispatch's prompts. Returns (``y
    * silu(gate)`` [N, d], state [prompts, d_state, d] float32 after each
    prompt's last real token)."""
    helper = LayerHelper("ssm_prefill_scan", name=name)
    out, state = _new(helper, x), _new(helper, x, "float32")
    helper.append_op(
        type="ssm_prefill_scan",
        inputs={"X": [x], "Delta": [delta], "B": [b], "C": [c],
                "ALog": [a_log], "DSkip": [d_skip], "Gate": [gate],
                "Lens": [lens]},
        outputs={"Out": [out], "State": [state]})
    return out, state


def ssm_conv_step(window, x, w, bias, live, name=None):
    """One token of the convolution for every slot; ``window`` is updated
    in place (a slot that is not live keeps its own)."""
    helper = LayerHelper("ssm_conv_step", name=name)
    out = _new(helper, x)
    helper.append_op(
        type="ssm_conv_step",
        inputs={"Window": [window], "X": [x], "W": [w], "Bias": [bias],
                "Live": [live]},
        outputs={"Out": [out], "WindowOut": [window]})
    return out


def ssm_state_update(state, x, delta, b, c, a_log, d_skip, gate, live,
                     name=None):
    """One token of the recurrence for every slot: ``y * silu(gate)``
    [S, d]; ``state`` is updated in place (a slot that is not live keeps
    its own and reads 0)."""
    helper = LayerHelper("ssm_state_update", name=name)
    out = _new(helper, x)
    helper.append_op(
        type="ssm_state_update",
        inputs={"State": [state], "X": [x], "Delta": [delta], "B": [b],
                "C": [c], "ALog": [a_log], "DSkip": [d_skip],
                "Gate": [gate], "Live": [live]},
        outputs={"Out": [out], "StateOut": [state]})
    return out


def slot_state_write(state, index, values, axis=0, name=None):
    """``state[index[b]] = values[b]`` along ``axis``, in place; an index
    past the last slot (a prefill batch's padding) writes nothing."""
    helper = LayerHelper("slot_state_write", name=name)
    helper.append_op(
        type="slot_state_write",
        inputs={"State": [state], "Index": [index], "Values": [values]},
        outputs={"StateOut": [state]}, attrs={"axis": int(axis)})
    return state


def gqa_prefill_attention(q, k, v, prompts, heads, kv_heads, name=None):
    """Causal grouped-query attention of ``prompts`` prompts of equal
    (bucket) length through the flash kernel: ``[N, heads * dh]``."""
    helper = LayerHelper("gqa_prefill_attention", name=name)
    out = _new(helper, q)
    helper.append_op(
        type="gqa_prefill_attention",
        inputs={"Q": [q], "K": [k], "V": [v]}, outputs={"Out": [out]},
        attrs={"prompts": int(prompts), "heads": int(heads),
               "kv_heads": int(kv_heads)})
    return out


def gqa_paged_attention(q, k_pool, v_pool, page_table, lengths, heads,
                        name=None):
    """Grouped-query decode attention of every slot over its K/V rows
    (``kernels/gqa_paged_attention.py``): ``[S, heads * dh]``."""
    helper = LayerHelper("gqa_paged_attention", name=name)
    out = _new(helper, q)
    helper.append_op(
        type="gqa_paged_attention",
        inputs={"Q": [q], "KPool": [k_pool], "VPool": [v_pool],
                "PageTable": [page_table], "Lengths": [lengths]},
        outputs={"Out": [out]}, attrs={"heads": int(heads)})
    return out


def tied_vocab_projection(x, table, name=None):
    """``x @ table^T`` in float32, ``table`` [vocab, hidden] the
    embedding (``tie_word_embeddings``)."""
    helper = LayerHelper("tied_vocab_projection", name=name)
    out = _new(helper, x, "float32")
    helper.append_op(type="tied_vocab_projection",
                     inputs={"X": [x], "W": [table]},
                     outputs={"Out": [out]})
    return out
