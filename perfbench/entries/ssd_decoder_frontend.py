"""Entry ``ssd_decoder_frontend``: a hybrid decoder-only model of Mamba-2
(state-space duality) layers with a few grouped-query attention layers
among them and a held shard of its routed experts in every layer, behind
the same ``ServingFrontend``, wire and clients as the other decoder-only
entries: a ``DecoderOnlySession`` (``serving/decoder_session.py``) over K/V
page pools for the attention layers and, a slot a Mamba-2 layer, a float32
matrix state a head and a convolution window; prompts of up to 4096 tokens
prefilled in buckets, the recurrence in chunks of 256. The run is
``decoder_family``'s; the model's own parts are ``serve_granite_common``'s."""

# a program that lacks these fails here, at once, with no child started
from paddle_tpu.kernels import ssd  # noqa: F401
from paddle_tpu.ops import ssd_ops  # noqa: F401

from perfbench import decoder_family, serve_granite_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
