"""Entry ``sparse_decoder_frontend``: a decoder-only model with latent
attention under LEARNED SPARSE attention and a held shard of its routed
experts, behind the same ``ServingFrontend``, wire and clients as the
other decoder-only entries: a ``DecoderOnlySession``
(``serving/decoder_session.py``) over latent row pools and, in the layers
that have an indexer, a narrower key pool under the same page table;
prompts of up to 16384 tokens prefilled in buckets. The run is
``decoder_family``'s; the model's own parts are ``serve_glm52_common``'s."""

# a program that lacks these fails here, at once, with no child started
from paddle_tpu.kernels import sparse_latent_attention  # noqa: F401
from paddle_tpu.ops import sparse_attention_ops  # noqa: F401

from perfbench import decoder_family, serve_glm52_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
