"""Serving host plane, above the knee: share of the rounds' host time in
which the decode worker's thread was not running (1 - thread CPU time /
wall): what it waits for the interpreter lock or the scheduler."""

from perfbench import program_records as pr


def read(records):
    return pr.read_rounds(records, pr.offcpu_share)
