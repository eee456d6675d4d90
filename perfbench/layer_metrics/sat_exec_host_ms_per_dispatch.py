"""Model step, above the knee: the host's time inside one executor
dispatch (the executor's dispatch records between the first and the
last round of the window: every phase but ``device`` and ``compile``),
the mean."""

from perfbench import program_records as pr


def read(records):
    return pr.read_serve_exec_host_ms(records)
