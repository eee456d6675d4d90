"""Serving host plane: median wait in the session's queue (the ``queue``
span of the program's request traces, FLAGS_request_tracing on in the
traced run), over the traces its ring still holds."""

from perfbench import metric_lib as lib


def read(records):
    return lib.trace_stat_p50_ms(records, 'queue_s')
