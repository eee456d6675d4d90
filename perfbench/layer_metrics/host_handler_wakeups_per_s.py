"""Serving host plane: the handler threads' wake-ups a wall second of the
window (the rounds' ``handler_wakeups``, last less first, over the time
between those rounds' ends): returns of a handler's ``stream.q.get()``,
one for every line it writes and one for a verdict of the watcher's. A
handler that polled its connection woke for every open stream on top.
Beside the chunks a second of the ledger's handlers line it says whether
the handlers wake to write and for nothing else."""

from perfbench import host_ledger


def read(records):
    return host_ledger.read_rounds_stat(
        records, host_ledger.handler_wakeups_per_s)
