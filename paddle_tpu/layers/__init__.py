"""User-facing layers API (python/paddle/fluid/layers parity)."""

from paddle_tpu.layers import math_ops  # noqa: F401
from paddle_tpu.layers.tensor import *  # noqa: F401,F403
from paddle_tpu.layers.ops import *  # noqa: F401,F403
from paddle_tpu.layers.nn import *  # noqa: F401,F403
from paddle_tpu.layers.io import *  # noqa: F401,F403
from paddle_tpu.layers.control_flow import *  # noqa: F401,F403
from paddle_tpu.layers.metric_op import *  # noqa: F401,F403
from paddle_tpu.layers.loss import *  # noqa: F401,F403
from paddle_tpu.layers import learning_rate_scheduler  # noqa: F401
from paddle_tpu.layers.learning_rate_scheduler import (  # noqa: F401
    exponential_decay,
    natural_exp_decay,
    inverse_time_decay,
    polynomial_decay,
    piecewise_decay,
    noam_decay,
    cosine_decay,
    append_LARS,
)
from paddle_tpu.layers.sequence import *  # noqa: F401,F403
from paddle_tpu.layers.rnn import *  # noqa: F401,F403
from paddle_tpu.layers.attention import *  # noqa: F401,F403
from paddle_tpu.layers.decoder import *  # noqa: F401,F403
from paddle_tpu.layers.ssm import *  # noqa: F401,F403
from paddle_tpu.layers.window import *  # noqa: F401,F403
from paddle_tpu.layers.nlp import *  # noqa: F401,F403
from paddle_tpu.layers.detection import *  # noqa: F401,F403
