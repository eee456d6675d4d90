"""Serving host plane: median length of the decode worker's rounds of the
window that dispatched (the program's ``round`` span): what a stream
waits between two chunks of tokens."""

from perfbench import program_records as pr


def read(records):
    return pr.read_rounds(records, pr.round_ms_p50)
