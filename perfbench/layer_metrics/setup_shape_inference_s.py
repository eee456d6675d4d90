"""Set-up: seconds the IR builder spent inferring shapes, one
``jax.eval_shape`` of the operator's lowering rule an appended operator
(the row ``infer_op_shapes`` of ``exec_cache.stats()["by_function"]``, at
the harness's copy): the part of ``trace_lower_s`` that building programs
costs before any of them is traced."""

from perfbench import setup_ledger


def read(records):
    return setup_ledger.read(records, "setup_shape_inference_s")
