"""Entry ``linear_latent_decoder_frontend``: a decoder-only model of gated
delta-rule linear-attention layers whose full-attention sibling is a NoPE
LATENT layer, with a leading dense layer and a held shard of its routed
experts, behind the same ``ServingFrontend``, wire and clients as the
other decoder-only entries: a ``DecoderOnlySession``
(``serving/decoder_session.py``) over ONE pool of latent rows for the
latent layer and, a slot a linear layer, a float32 matrix state a head and
a convolution window; prompts of up to 4096 tokens prefilled in buckets,
the delta rule in chunks. The run is ``decoder_family``'s; the model's own
parts are ``serve_kimi_common``'s."""

# a program that lacks these fails here, at once, with no child started
from paddle_tpu.kernels import delta_rule, latent_attention  # noqa: F401
from paddle_tpu.models.linear_attn_moe_decoder import LATENT  # noqa: F401

from perfbench import decoder_family, serve_kimi_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
