"""Serving host plane: the handler threads' CPU seconds a wall second of
the window x 100 (the rounds' ``handler_cpu``, last less first, over the
time between those rounds' ends). 100 is one core, which is all the
interpreter lock gives the process's Python; the handlers' socket writes,
which hold no lock, are in it."""

from perfbench import host_ledger


def read(records):
    return host_ledger.read_handler_cpu_share(records)
