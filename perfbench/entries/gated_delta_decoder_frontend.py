"""Entry ``gated_delta_decoder_frontend``: a DENSE decoder-only model of
Gated DeltaNet linear-attention layers (a decay a head, keys of 96 beside
values of 192) with a multi-head attention layer every fourth, behind the
same ``ServingFrontend``, wire and clients as the other decoder-only
entries: a ``DecoderOnlySession`` (``serving/decoder_session.py``) over K
and V page pools for the full layers and, a slot a linear layer, a float32
matrix state a head and a convolution window; prompts of up to 1024 tokens
prefilled in buckets, the delta rule in chunks. The run is
``decoder_family``'s; the model's own parts are ``serve_olmo_common``'s."""

# a program that lacks these fails here, at once, with no child started
from paddle_tpu.kernels.delta_rule import pack_heads  # noqa: F401
from paddle_tpu.models import gated_delta_decoder  # noqa: F401

from perfbench import decoder_family, serve_olmo_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
