"""Model step: the token places the prefill dispatches walked for nothing
(their programs' ``rows x bucket`` less the prompts' own tokens), of all
they walked: the rounds' ``prefill_pad_tokens``; the chunks the Mamba-2
prefill walked and skipped (``prefill_chunks`` / ``prefill_chunks_padded``)
are printed on an earlier line."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.read_prefill_pad_share(records)
