"""Set-up: seconds under the program's root set-up spans: in the
Transformer's session the constructor (``session.init``: every program
built as IR, the pools made on the device, the ladders' programs built AND
first run), in the decoder-only session the builder's spans a program
(``init``, ``prefill/<bucket>``, ``step``: IR building) and ``pools``. The
part of the harness's ``program_build`` that is the program's own; the
printed ledger has it by span."""

from perfbench import setup_ledger


def read(records):
    return setup_ledger.read(records, "setup_spans_s")
