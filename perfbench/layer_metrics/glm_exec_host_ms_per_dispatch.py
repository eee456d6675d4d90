"""Model step: the host's time inside one executor dispatch, prefill and
decode alike (the executor's dispatch records between the first and the
last round of the window: every phase but ``device`` and ``compile``),
the mean."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.exec_host_ms_per_dispatch(records)
