"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (reference: /root/reference, czhu15/Paddle ~v1.5).

Design (see SURVEY.md): a static-graph ``Program`` IR is built by a Python layers
DSL (mirroring ``python/paddle/fluid/framework.py``), but execution is TPU-native:
the Executor lowers a whole block to a single jaxpr and caches the ``jax.jit``
compilation, instead of interpreting ops one by one against a mutable Scope
(reference: ``paddle/fluid/framework/executor.cc:416``).  Autodiff is
program-level reverse mode (``append_backward``) like the reference's
``python/paddle/fluid/backward.py``, with per-op grad rules derived from the op's
own XLA lowering via ``jax.vjp``.  Multi-device/multi-host training uses GSPMD
(`jax.jit` over a ``jax.sharding.Mesh``) in place of the reference's
ParallelExecutor/NCCL op-handle machinery.
"""

def _configure_jax():
    """TPU-friendly jax defaults, set before first trace.

    - rbg PRNG: the default threefry generator is counter-based and slow on
      TPU (the dropout masks alone cost ~25% of a BERT step); rbg uses the
      hardware RNG path and is the jax-recommended choice for dropout-class
      randomness on TPU.
    """
    import jax

    jax.config.update("jax_default_prng_impl", "rbg")


_configure_jax()

from . import core
from . import average
from . import analysis
from . import trainer_desc
from . import device_worker
from . import evaluator
from .framework import (
    Program,
    Block,
    Operator,
    Variable,
    Parameter,
    program_guard,
    name_scope,
    default_main_program,
    default_startup_program,
    switch_main_program,
    switch_startup_program,
    cpu_places,
    cuda_places,
    tpu_places,
    device_places,
    in_dygraph_mode,
)
from .executor import Executor, global_scope, scope_guard, Scope
from .param_attr import ParamAttr, WeightNormParamAttr
from .data_feeder import DataFeeder
from .core import CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace, set_flags, get_flags
from .backward import append_backward, gradients
from .compiler import CompiledProgram, BuildStrategy, ExecutionStrategy
from . import layers
from . import initializer
from . import optimizer
from . import regularizer
from . import clip
from . import nets
from . import metrics
from . import io
from . import unique_name
from . import dygraph
from . import profiler
from . import contrib
from . import pipeline
from . import reader
from . import native
from . import recordio_writer
from . import inference
from . import reader_decorators
from . import dygraph_grad_clip
from . import install_check
from . import host_table
from . import autotune
from .lod_tensor import (LoDTensor, LoDTensorArray, create_lod_tensor,
                         create_random_int_lodtensor)
from .transpiler import memory_optimize, release_memory
from . import datasets
from .reader_decorators import batch
from .reader import PyReader, DataLoader
from .io import (
    save_vars,
    save_params,
    save_persistables,
    load_vars,
    load_params,
    load_persistables,
    save_inference_model,
    load_inference_model,
)
from .initializer import set_global_initializer  # noqa: F401
from .clip import GradientClipByGlobalNorm, GradientClipByNorm, GradientClipByValue
from .parallel import ParallelExecutor
from .dygraph.base import enable_dygraph, disable_dygraph
from . import transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig
from .data_feed_desc import DataFeedDesc
from .dataset import DatasetFactory
from . import static_analysis
from .static_analysis import analyze_program, verify_program
from . import resilience

# `import paddle_tpu as fluid` is the intended spelling for users of the
# reference's `import paddle.fluid as fluid`.
fluid = __import__(__name__)

__version__ = "0.1.0"

__all__ = [
    "Program",
    "Block",
    "Operator",
    "Variable",
    "Parameter",
    "program_guard",
    "name_scope",
    "default_main_program",
    "default_startup_program",
    "Executor",
    "ParallelExecutor",
    "CompiledProgram",
    "BuildStrategy",
    "ExecutionStrategy",
    "global_scope",
    "scope_guard",
    "Scope",
    "ParamAttr",
    "WeightNormParamAttr",
    "DataFeeder",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "CUDAPinnedPlace",
    "append_backward",
    "gradients",
    "DistributeTranspiler",
    "DistributeTranspilerConfig",
    "DataFeedDesc",
    "DatasetFactory",
    "layers",
    "initializer",
    "optimizer",
    "regularizer",
    "clip",
    "nets",
    "metrics",
    "io",
    "reader",
    "pipeline",
    "PyReader",
    "DataLoader",
    "unique_name",
    "dygraph",
    "profiler",
    "contrib",
    "cpu_places",
    "cuda_places",
    "tpu_places",
    "dygraph_grad_clip",
    "install_check",
    "in_dygraph_mode",
    "host_table",
    "autotune",
    "LoDTensor",
    "LoDTensorArray",
    "create_lod_tensor",
    "create_random_int_lodtensor",
    "memory_optimize",
    "release_memory",
    "is_compiled_with_cuda",
    "cuda_pinned_places",
    "static_analysis",
    "verify_program",
    "analyze_program",
    "resilience",
]


def is_compiled_with_cuda():
    """reference fluid.is_compiled_with_cuda — this backend is XLA/TPU."""
    return False


def cuda_pinned_places(device_count=None):
    """reference fluid.cuda_pinned_places: pinned host staging areas are
    XLA's job on TPU; returns CPU places for API compatibility."""
    return cpu_places(device_count)
