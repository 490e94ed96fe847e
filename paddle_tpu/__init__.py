"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle Fluid (reference: tim-lee-cn/Paddle), rebuilt on
JAX/XLA/Pallas.

Design (vs. reference paddle/fluid/framework/executor.cc:133 op-by-op
interpreter): Python builds a Program IR of blocks/ops/vars, and the Executor
TRACES an entire block into one pure JAX function — (state, feeds) ->
(fetches, new_state) — and jit-compiles it with XLA, so a full training step
(forward + backward + optimizer) is a single fused TPU computation. Per-op
"kernels" are JAX callables in an op registry; gradients are built at the IR
level by per-op grad makers (reference: backward.py:434 append_backward) with
an automatic jax.vjp fallback; optimizers emit optimizer ops into the program
(reference: optimizer.py:231 minimize).
"""

from . import core
from .core import framework
from .core.framework import (
    Program,
    Block,
    Operator,
    Variable,
    Parameter,
    default_main_program,
    default_startup_program,
    program_guard,
    switch_main_program,
    switch_startup_program,
    name_scope,
)
from .core.places import CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace, is_compiled_with_tpu
from .core.scope import Scope, global_scope, scope_guard
from .core.lod_tensor import (LoDTensor, create_bucketed_seq_tensor,
                              create_lod_tensor, create_random_int_lodtensor)
from .executor import Executor, fetch_var
from .parallel_executor import ParallelExecutor, ExecutionStrategy, BuildStrategy
from . import layers
from . import nets
from . import ops  # registers all op kernels
from . import initializer
from . import regularizer
from . import clip
from . import metrics
from . import evaluator
from . import profiler
from . import io
from . import debugger
from .io import (
    save_vars,
    save_params,
    save_persistables,
    load_vars,
    load_params,
    load_persistables,
    save_inference_model,
    load_inference_model,
    save_checkpoint,
    load_checkpoint,
    clean_checkpoint,
)
from .backward import append_backward, calc_gradient
from .optimizer import (
    SGD,
    ProximalGD,
    ProximalAdagrad,
    Momentum,
    Adagrad,
    Adam,
    Adamax,
    DecayedAdagrad,
    Adadelta,
    RMSProp,
    SGDOptimizer,
    MomentumOptimizer,
    AdagradOptimizer,
    AdamOptimizer,
    AdamaxOptimizer,
    DecayedAdagradOptimizer,
    AdadeltaOptimizer,
    RMSPropOptimizer,
    ModelAverage,
    Optimizer,
)
from .param_attr import ParamAttr, WeightNormParamAttr
from .data_feeder import DataFeeder
from .trainer import Trainer, BeginEpochEvent, EndEpochEvent, BeginStepEvent, EndStepEvent
from .inferencer import Inferencer
from . import amp
from . import flags
from . import concurrency
from . import transpiler
from .transpiler import DistributeTranspiler, InferenceTranspiler, memory_optimize, release_memory
from .unique_name import generate as _generate_unique_name
from . import unique_name
from . import reader
from . import pipeline
from .pipeline import DeviceChunkFeeder
from . import datapipe
from .datapipe import DataPipe, AsyncDeviceFeeder
from . import monitor
from . import analysis
from . import health
from . import resilience
from .resilience import ResilienceConfig, ResilientRunner
from . import dataset
from . import parallel
from . import serve
from . import trace
from .minibatch import batch

Tensor = LoDTensor

__version__ = "0.1.0"

__all__ = [
    "Program", "Block", "Operator", "Variable", "Parameter",
    "default_main_program", "default_startup_program", "program_guard",
    "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
    "Scope", "global_scope", "scope_guard",
    "LoDTensor", "Tensor", "create_lod_tensor", "create_random_int_lodtensor",
    "create_bucketed_seq_tensor",
    "Executor", "fetch_var", "ParallelExecutor", "ExecutionStrategy", "BuildStrategy",
    "layers", "nets", "ops", "initializer", "regularizer", "clip",
    "metrics", "evaluator", "profiler", "io", "debugger",
    "append_backward", "calc_gradient",
    "ParamAttr", "WeightNormParamAttr", "DataFeeder",
    "Trainer", "Inferencer", "transpiler", "DistributeTranspiler",
    "InferenceTranspiler", "memory_optimize", "release_memory",
    "reader", "dataset", "batch", "unique_name", "parallel", "flags",
    "concurrency", "pipeline", "DeviceChunkFeeder", "datapipe", "DataPipe",
    "AsyncDeviceFeeder", "monitor", "health", "resilience",
    "ResilienceConfig", "ResilientRunner", "serve", "trace",
]
