"""Operator definitions: schema + XLA lowering per op family.

Reference parity: ``paddle/fluid/operators/`` (~748 files). Importing this
package registers every op with the registry; the kernel body of each op is
a JAX/XLA lowering (and Pallas for hand-tuned hot paths) instead of
CPU/CUDA kernels.
"""

from paddle_tpu.ops import math_ops  # noqa: F401
from paddle_tpu.ops import tensor_ops  # noqa: F401
from paddle_tpu.ops import activation_ops  # noqa: F401
from paddle_tpu.ops import random_ops  # noqa: F401
from paddle_tpu.ops import loss_ops  # noqa: F401
from paddle_tpu.ops import nn_ops  # noqa: F401
from paddle_tpu.ops import optimizer_ops  # noqa: F401
from paddle_tpu.ops import control_flow_ops  # noqa: F401
from paddle_tpu.ops import subblock_ops  # noqa: F401
from paddle_tpu.ops import rnn_ops  # noqa: F401
from paddle_tpu.ops import attention_ops  # noqa: F401
from paddle_tpu.ops import sequence_ops  # noqa: F401
from paddle_tpu.ops import metric_ops  # noqa: F401
from paddle_tpu.ops import io_ops  # noqa: F401
from paddle_tpu.ops import detection_ops  # noqa: F401
from paddle_tpu.ops import beam_search_ops  # noqa: F401
from paddle_tpu.ops import seq2seq_ops  # noqa: F401
from paddle_tpu.ops import crf_ops  # noqa: F401
from paddle_tpu.ops import ctc_ops  # noqa: F401
from paddle_tpu.ops import sampling_ops  # noqa: F401
from paddle_tpu.ops import speculative_ops  # noqa: F401
from paddle_tpu.ops import vision_ops  # noqa: F401
from paddle_tpu.ops import quantize_ops  # noqa: F401
from paddle_tpu.ops import fused_ops  # noqa: F401
from paddle_tpu.ops import moe_ops  # noqa: F401
from paddle_tpu.ops import decoder_ops  # noqa: F401
from paddle_tpu.ops import sparse_attention_ops  # noqa: F401
from paddle_tpu.ops import ssm_ops  # noqa: F401
from paddle_tpu.ops import linear_attention_ops  # noqa: F401
from paddle_tpu.ops import window_ops  # noqa: F401
from paddle_tpu.ops import ssd_ops  # noqa: F401
