"""Set-up: programs built, weights and pools made on the device, and
the reference check (seconds of the host's clock)."""

from perfbench import metric_lib as lib


def read(records):
    return lib.setup_seconds(records, 'program_build', 'startup_init',
                             'reference_check')
