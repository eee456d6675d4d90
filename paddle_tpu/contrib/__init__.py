"""Contrib utilities (python/paddle/fluid/contrib parity).

memory_usage   - estimate a Program's device-memory band for a batch size
                 (contrib/memory_usage_calc.py role).
op_freq_statis - unigram + adjacent-pair op frequency statistics
                 (contrib/op_frequence.py role).
QuantizeTranspiler is re-exported from transpiler (the contrib/quantize
package's home in the reference); the contrib beam-search decoder's
capability lives in ops/beam_search_ops.py + layers.
"""

from collections import OrderedDict

from paddle_tpu.transpiler.quantize_transpiler import (  # noqa: F401
    QuantizeTranspiler,
)

__all__ = ["memory_usage", "op_freq_statistic", "op_freq_statis",
           "QuantizeTranspiler", "InitState", "StateCell",
           "TrainingDecoder", "BeamSearchDecoder"]

_DTYPE_SIZE = {
    "float16": 2, "bfloat16": 2, "float32": 4, "float64": 8,
    "int16": 2, "int32": 4, "int64": 8, "bool": 1, "uint8": 1, "int8": 1,
}

# The reference reports a 70%-100% band of the summed var sizes (memory
# reuse makes the true footprint land inside it); same convention here.
_LOWER_FRACTION = 0.7


def memory_usage(program, batch_size):
    """Estimate `program`'s tensor memory for `batch_size` rows.

    Returns (lower, upper, unit): the estimated band, scaled to the
    largest of B/KB/MB/GB. -1 leading dims are replaced by batch_size.
    Under XLA the true footprint is the compiled executable's (buffer
    reuse + donation below this bound); this is the graph-level estimate
    the reference tooling exposes.
    """
    from paddle_tpu import framework

    if not isinstance(program, framework.Program):
        raise TypeError(
            "memory_usage expects a Program, got %s" % type(program))
    if int(batch_size) <= 0:
        raise ValueError("batch_size must be positive")

    total = 0.0
    for var in program.list_vars():
        shape = list(var.shape or ())
        if not shape:
            continue
        count = 1
        for d in shape:
            d = int(d)
            count *= batch_size if d < 0 else d
        total += count * _DTYPE_SIZE.get(str(var.dtype), 4)

    unit = "B"
    for next_unit in ("KB", "MB", "GB"):
        if total < 1024:
            break
        total /= 1024.0
        unit = next_unit
    return total * _LOWER_FRACTION, total, unit


def op_freq_statis(program):
    """Op frequency statistics: (unigram, adjacent-pair) OrderedDicts,
    most frequent first. Pairs are "producer->consumer" op types chained
    through non-parameter vars — the hot-path fusion-candidate report of
    the reference tool."""
    from paddle_tpu import framework

    if not isinstance(program, framework.Program):
        raise TypeError(
            "op_freq_statis expects a Program, got %s" % type(program))

    params = {p.name for p in program.global_block().all_parameters()}
    uni = {}
    var_producer = {}
    pair = {}
    for op in program.global_block().ops:
        uni[op.type] = uni.get(op.type, 0) + 1
        for name in op.input_arg_names():
            prev = var_producer.get(name)
            if prev is not None and name not in params:
                key = "%s->%s" % (prev, op.type)
                pair[key] = pair.get(key, 0) + 1
        for name in op.output_arg_names():
            if name not in params:
                var_producer[name] = op.type
    order = lambda d: OrderedDict(
        sorted(d.items(), key=lambda kv: -kv[1]))
    return order(uni), order(pair)


from paddle_tpu.contrib.decoder import (  # noqa: E402,F401
    BeamSearchDecoder,
    InitState,
    StateCell,
    TrainingDecoder,
)

# reference name (contrib/op_frequence.py:op_freq_statistic); the
# shorter alias predates the rename and is kept for compatibility
op_freq_statistic = op_freq_statis
