"""Device, above the knee: share of chip 0's idle time that lies under no
program span (``pt:`` annotations in the host plane), or under the bare
``round`` with no child open. The whole table, idle seconds by innermost
span, is printed on an earlier line."""

from perfbench import program_records as pr


def read(records):
    return pr.read_idle_unattributed(records)
