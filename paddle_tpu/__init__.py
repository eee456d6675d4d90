"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle-Fluid
capabilities.

Design (see SURVEY.md): the user-visible contract is Fluid's declarative
Program/Block/Operator graph built from Python ``layers.*`` calls with
``append_backward`` graph-level autodiff and optimizer *ops* — but the
execution engine is a whole-program XLA compiler: ``Executor(TPUPlace())``
lowers the entire op graph to one JAX function, ``jax.jit``-compiles it once
per (program, feed-shapes, mesh) and caches the executable. Multi-device
training is GSPMD sharding over a ``jax.sharding.Mesh`` (ParallelExecutor),
not per-op kernel dispatch + NCCL as in the CUDA reference.

Reference parity: python/paddle/fluid/__init__.py in reyoung/Paddle.
"""

from paddle_tpu.core.types import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    NoAcceleratorError,
    TPUPlace,
    Place,
    VarType,
    core_version,
    require_accelerator,
)
from paddle_tpu import framework  # noqa: F401
from paddle_tpu import ops as _ops  # noqa: F401  (registers all operators)
from paddle_tpu.framework import (  # noqa: F401
    Program,
    Variable,
    Parameter,
    default_main_program,
    default_startup_program,
    program_guard,
    name_scope,
    cpu_places,
    tpu_places,
)
from paddle_tpu import initializer  # noqa: F401
from paddle_tpu import layers  # noqa: F401
from paddle_tpu import nets  # noqa: F401
from paddle_tpu import backward  # noqa: F401
from paddle_tpu.backward import append_backward, calc_gradient  # noqa: F401
from paddle_tpu import optimizer  # noqa: F401
from paddle_tpu import regularizer  # noqa: F401
from paddle_tpu import clip  # noqa: F401
from paddle_tpu import metrics  # noqa: F401
from paddle_tpu import evaluator  # noqa: F401
from paddle_tpu import recordio_writer  # noqa: F401
from paddle_tpu import profiler  # noqa: F401
from paddle_tpu.executor import Executor, global_scope, scope_guard  # noqa: F401
from paddle_tpu.parallel_executor import (  # noqa: F401
    ParallelExecutor,
    BuildStrategy,
    ExecutionStrategy,
)
from paddle_tpu.data_feeder import DataFeeder  # noqa: F401
from paddle_tpu import io  # noqa: F401
from paddle_tpu import inference  # noqa: F401
from paddle_tpu import transpiler  # noqa: F401
from paddle_tpu import flags  # noqa: F401
from paddle_tpu import resilience  # noqa: F401
from paddle_tpu import debugger  # noqa: F401
from paddle_tpu import analysis  # noqa: F401
from paddle_tpu.core import passes  # noqa: F401
from paddle_tpu.transpiler import memory_optimize, release_memory  # noqa: F401
from paddle_tpu.transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from paddle_tpu.core.lod import (  # noqa: F401
    LoDTensor,
    create_lod_tensor,
    create_random_int_lodtensor,
)
from paddle_tpu import average  # noqa: F401
from paddle_tpu.core.selected_rows import SelectedRows  # noqa: F401
from paddle_tpu import unique_name  # noqa: F401
from paddle_tpu.param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from paddle_tpu import contrib  # noqa: F401
from paddle_tpu.executor import Scope  # noqa: F401
from paddle_tpu.layers import learning_rate_scheduler as learning_rate_decay  # noqa: F401,E501
from paddle_tpu.layers.control_flow import LoDTensorArray  # noqa: F401
from paddle_tpu import serving  # noqa: F401
from paddle_tpu import elastic  # noqa: F401

__version__ = "0.1.0"

Tensor = LoDTensor
