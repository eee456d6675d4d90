"""Set-up: seconds JAX spent tracing and lowering the program's own
executables' steps before the window opened (the rows ``split_step`` and
``multi`` of ``exec_cache.stats()["by_function"]``, at the harness's
copy): the part of ``trace_lower_s`` that is the served programs', less
the helpers jitted on their own inside them."""

from perfbench import setup_ledger


def read(records):
    return setup_ledger.read(records, "setup_step_trace_lower_s")
