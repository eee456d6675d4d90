"""Set-up: programs the persistent cache did not hold (a warm run reads
0), from ``exec_cache.stats()`` at the window's opening."""


def read(records):
    return records['cache']['persistent_misses']
