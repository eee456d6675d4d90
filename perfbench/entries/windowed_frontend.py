"""Entry ``windowed_frontend``: a decoder of sliding-window and full
attention layers with routed experts behind the same ``ServingFrontend``,
wire and clients as entries ``frontend``, ``decoder_frontend`` and
``hybrid_frontend``: a ``DecoderOnlySession``
(``serving/decoder_session.py``) whose slots own a ring of K/V pages a
window layer beside a full layer's growing pages, prompts of up to 8192
tokens prefilled in buckets. The run is ``decoder_family``'s; the model's
own parts are ``serve_trinity_common``'s."""

# a program that lacks these fails here, at once, with no child started
from paddle_tpu.kernels import window_paged_attention  # noqa: F401
from paddle_tpu.models.windowed_moe_decoder import build_windowed_moe_decoder  # noqa: F401

from perfbench import decoder_family, serve_trinity_common as common


def run(ctx):
    return decoder_family.run(ctx, common)


def make_checker(cell, devices):
    return decoder_family.make_checker(cell, devices, common)
