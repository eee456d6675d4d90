"""Entry ``hybrid_frontend``: a hybrid state-space / attention decoder
behind the same ``ServingFrontend``, wire and clients as entries
``frontend`` and ``decoder_frontend``: a ``DecoderOnlySession``
(``serving/decoder_session.py``) whose slots own recurrent state beside
K/V pages, prompts prefilled several a dispatch. The run is
``decoder_family``'s; the model's own parts are ``serve_jamba_common``'s."""

# a program that lacks these fails here, at once, with no child started
from paddle_tpu.kernels import gqa_paged_attention, selective_scan  # noqa: F401
from paddle_tpu.models.hybrid_ssm_decoder import build_hybrid_ssm_decoder  # noqa: F401

from perfbench import decoder_family, serve_jamba_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
