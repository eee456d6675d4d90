"""Model step: the host's time inside one ``Executor.run`` dispatch as the
executor itself brackets it (its dispatch records: every phase but
``device`` and ``compile``), the mean over the window's steps. The inside
twin of ``train_dispatch_host_ms``."""

from perfbench import program_records as pr


def read(records):
    return pr.read_train_exec_host_ms(records)
