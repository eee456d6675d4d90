"""Model step: mean CPU time of the calling thread in an executor call's
host phases (all but ``device`` and ``compile``), over the dispatch
records between the window's rounds: the half of a call that is work,
Python and the runtime's enqueue alike."""

from perfbench import host_ledger


def read(records):
    return host_ledger.read_call_mean(records, "cpu_ms")
