"""paddle_tpu: a TPU-native deep-learning framework with the capability
surface of PaddlePaddle Fluid (reference mounted at /root/reference).

The user-facing API mirrors ``paddle.fluid``:

    import paddle_tpu.fluid as fluid
    x = fluid.layers.data('x', [784])
    y = fluid.layers.fc(x, 10, act='softmax')
    ...
    exe = fluid.Executor(fluid.TPUPlace(0))

Design: a Python graph IR (framework.py) lowers wholesale into single
jitted XLA modules (core/lowering.py, executor.py); distributed training
uses jax.sharding meshes + GSPMD instead of NCCL rings (parallel/).
"""
import time as _time

_T_IMPORT = _time.perf_counter()  # before the first import below

from paddle_tpu import framework
from paddle_tpu.framework import (
    CPUPlace,
    CUDAPlace,
    Place,
    Program,
    TPUPlace,
    cpu_places,
    cuda_pinned_places,
    cuda_places,
    is_compiled_with_cuda,
    default_main_program,
    default_startup_program,
    in_dygraph_mode,
    name_scope,
    program_guard,
)
from paddle_tpu.executor import AsyncExecutor, Executor
from paddle_tpu.scope import Scope, global_scope, scope_guard

from paddle_tpu import (
    backward,
    clip,
    initializer,
    layers,
    metrics,
    optimizer,
    regularizer,
    unique_name,
)
from paddle_tpu.backward import append_backward, gradients
from paddle_tpu.param_attr import ParamAttr, WeightNormParamAttr
from paddle_tpu import parallel
from paddle_tpu import dygraph
from paddle_tpu import distributed
from paddle_tpu import transpiler
from paddle_tpu.transpiler import (
    DistributeTranspiler,
    DistributeTranspilerConfig,
    InferenceTranspiler,
)
from paddle_tpu import contrib
from paddle_tpu import inference
from paddle_tpu import native
from paddle_tpu.fluid_dataset import DatasetFactory, InMemoryDataset, QueueDataset
from paddle_tpu import monitor
from paddle_tpu import profiler
from paddle_tpu import serving
from paddle_tpu import sharding
from paddle_tpu import memory
from paddle_tpu import trainer_desc
from paddle_tpu.trainer_desc import TrainerFactory
from paddle_tpu import io_fs
from paddle_tpu import incubate
from paddle_tpu import io
from paddle_tpu import reader
from paddle_tpu import dataset
from paddle_tpu import flags
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu import nets
from paddle_tpu import dygraph_grad_clip
from paddle_tpu import recordio_writer
from paddle_tpu.parallel.compiled_program import ParallelExecutor
from paddle_tpu.optimizer import ExponentialMovingAverage
from paddle_tpu import install_check
from paddle_tpu.layers import learning_rate_scheduler as learning_rate_decay

# LoDTensor/Tensor surface: device arrays ARE the tensors on this build;
# the scope's tensor view carries the set/shape API (reference
# lod_tensor.h analog lives in the padded encoding, SURVEY.md §7)
from paddle_tpu.scope import _TensorView as Tensor

LoDTensor = Tensor
LoDTensorArray = list
from paddle_tpu.reader import PyReader, batch
from paddle_tpu.data_feeder import DataFeeder
from paddle_tpu.io import (
    load_inference_model,
    load_params,
    load_persistables,
    load_vars,
    save_inference_model,
    save_params,
    save_persistables,
    save_program,
    save_vars,
)
from paddle_tpu.parallel.compiled_program import CompiledProgram
from paddle_tpu.parallel.strategy import (
    BuildStrategy,
    DistributedStrategy,
    ExecutionStrategy,
)

__version__ = "0.1.0"

# what importing this package cost the process (jax's own import too,
# where this import is the first to load jax): of a start-up's import
# time, the rest is the interpreter, jax and libtpu coming up
monitor.gauge(
    "paddle_tpu_import_seconds",
    "wall seconds the import of the paddle_tpu package took in this "
    "process").set(_time.perf_counter() - _T_IMPORT)


def CUDAPinnedPlace():  # API parity shim
    return CPUPlace()
