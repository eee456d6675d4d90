"""Serving host plane: share of the decode worker's rounds, less their
``wait``, in which its thread was BLOCKED: neither running (``cpu``) nor
waiting for the chip inside the executor (the ``device`` phase of the
dispatch records inside the round). The interpreter lock by elimination;
``host_ledger.py`` says what else can hide in it."""

from perfbench import host_ledger


def read(records):
    return host_ledger.read_lockwait_share(records)
