"""Layers of a hybrid decoder-only block (``ops/ssm_ops.py``): the
selective state-space mixer's pieces in their prefill and one-token forms,
and grouped-query attention over paged K/V row pools; and a Mamba-2
mixer's (``ops/ssd_ops.py``). Like ``layers/decoder.py`` they take flat
token rows ``[N, ...]`` and the parameters as variables that the serving
builder (``models/hybrid_ssm_decoder.py``, ``models/ssd_moe_decoder.py``)
declares by name."""

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
    "ssd_prefill",
    "ssd_state_update",
    "gated_row_norm",
    "scaled_residual",
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


def _ssd_inputs(xbc, dt, dt_bias, a_log, d_skip):
    return {"XBC": [xbc], "Dt": [dt], "DtBias": [dt_bias], "ALog": [a_log],
            "DSkip": [d_skip]}


def ssd_prefill(xbc, dt, dt_bias, a_log, d_skip, lens, d_state, name=None):
    """The Mamba-2 recurrence over a prefill dispatch's prompts, in chunks
    (``kernels/ssd.py``): ``xbc`` [N, heads * d_head + 2 d_state] the
    convolved ``x | B | C`` row, ``dt`` [N, heads] the raw step. Returns (y
    [N, heads * d_head] float32, state float32 after each prompt's last
    real token, ``[prompts, lane groups, d_state, group lanes]``:
    ``kernels/ssd.py`` has the layout)."""
    helper = LayerHelper("ssd_prefill", name=name)
    out, state = _new(helper, xbc, "float32"), _new(helper, xbc, "float32")
    helper.append_op(
        type="ssd_prefill",
        inputs=dict(_ssd_inputs(xbc, dt, dt_bias, a_log, d_skip),
                    Lens=[lens]),
        outputs={"Out": [out], "State": [state]},
        attrs={"d_state": int(d_state)})
    return out, state


def ssd_state_update(state, xbc, dt, dt_bias, a_log, d_skip, live, d_state,
                     name=None):
    """One token of the Mamba-2 recurrence for every slot: y [S, heads *
    d_head] float32; ``state`` is updated in place (a slot that is not
    live keeps its own and reads 0)."""
    helper = LayerHelper("ssd_state_update", name=name)
    out = _new(helper, xbc, "float32")
    helper.append_op(
        type="ssd_state_update",
        inputs=dict(_ssd_inputs(xbc, dt, dt_bias, a_log, d_skip),
                    State=[state], Live=[live]),
        outputs={"Out": [out], "StateOut": [state]},
        attrs={"d_state": int(d_state)})
    return out


def gated_row_norm(x, gate, scale, epsilon=1e-5, name=None):
    """``RMSNorm(x * silu(gate)) * scale`` over the whole row, the gate
    multiplied in BEFORE the statistics (Mamba-2's gated norm with one
    group); in ``gate``'s dtype. ``layers.gated_head_norm`` is the norm
    first, a head at a time, with a sigmoid gate."""
    helper = LayerHelper("gated_row_norm", name=name)
    out = _new(helper, gate)
    helper.append_op(
        type="gated_row_norm",
        inputs={"X": [x], "Gate": [gate], "Scale": [scale]},
        outputs={"Out": [out]}, attrs={"epsilon": float(epsilon)})
    return out


def scaled_residual(x, y, scale, name=None):
    """``x + scale * y`` computed in float32 and rounded once (a model
    whose branches enter the residual stream through a multiplier)."""
    helper = LayerHelper("scaled_residual", name=name)
    out = _new(helper, x)
    helper.append_op(type="scaled_residual", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"scale": float(scale)})
    return out
