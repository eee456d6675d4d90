"""Serving host plane: the connection watcher's ``cancel`` verdicts of the
window (the rounds' ``watcher_cancel``, last less first) over the slots
its rounds' cancels released (``cancel_rows``). 1.0: every released slot
was a cancel line the watcher read as it arrived and handed to the decode
worker; under 1, cancels reach the worker by another door (a handler's own
read, a closed connection); None where nothing was cancelled.
``host_ledger.py`` says what moves it at the window's edges."""

from perfbench import host_ledger


def read(records):
    return host_ledger.read_rounds_stat(
        records, host_ledger.watcher_verdicts_per_cancel_row)
