"""Model step: mean wall less the calling thread's CPU of an executor
call's host phases (all but ``device`` and ``compile``), over the dispatch
records between the window's rounds: what a call waits, the half of the
host's time in a dispatch that is not work."""

from perfbench import host_ledger


def read(records):
    return host_ledger.read_call_mean(records, "blocked_ms")
