"""Device: share of the chip's idle time that lies under no program span
(``pt:`` annotations of the decode worker's thread), or under the bare
``round`` with no child open; each idle gap is laid at the spans it
overlaps, by overlap (``metric_lib_glm.lay_idle`` says why not at one
span). The whole table, idle seconds by span, is printed on an earlier
line."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.idle_unattributed_share(records)
