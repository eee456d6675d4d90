"""Set-up: seconds the process spent compiling or loading compiled
programs, from ``exec_cache.stats()`` at the window's opening."""


def read(records):
    return records['cache']['compile_seconds']
