"""Serving host plane: median of a dispatching round's host time: the
``round`` span less its ``wait``, ``step.dispatch`` and
``prefill.dispatch`` children."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.read_rounds(records, lib.round_host_ms_p50, log=True)
