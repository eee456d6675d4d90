"""Entry ``decoder_frontend``: a decoder-only model with latent attention
and routed experts behind the same ``ServingFrontend``, wire and clients
as entry ``frontend``: a ``DecoderOnlySession``
(``serving/decoder_session.py``) over a paged pool of latent rows, prompts
prefilled several a dispatch. The run is ``decoder_family``'s; the
model's own parts are ``serve_glm_common``'s."""

# a program that lacks these fails here, at once, with no child started
from paddle_tpu.kernels import latent_attention  # noqa: F401
from paddle_tpu.serving.decoder_session import DecoderOnlySession  # noqa: F401

from perfbench import decoder_family, serve_glm_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
