"""Entry ``shortcut_decoder_frontend``: a decoder-only model whose layer
holds TWO latent-attention blocks and two dense feed-forwards with the
routed-expert block on a shortcut across them, its router a softmax over
real and zero-compute (identity) experts, a held shard of the real ones,
behind the same ``ServingFrontend``, wire and clients as the other
decoder-only entries: a ``DecoderOnlySession``
(``serving/decoder_session.py``) over two latent row pools a layer under
one page table; prompts of up to 4096 tokens prefilled in buckets through
the flash forward at a value width of its own. The run is
``decoder_family``'s; the model's own parts are ``serve_longcat_common``'s."""

# a program that lacks this fails here, at once, with no child started
from paddle_tpu.models import shortcut_moe_decoder  # noqa: F401

from perfbench import decoder_family, serve_longcat_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
