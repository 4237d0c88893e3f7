"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid 1.2 (reference at /root/reference; blueprint in SURVEY.md).

Import surface mirrors `paddle.fluid`:

    import paddle_tpu as fluid
    x = fluid.layers.data('x', shape=[13])
    y = fluid.layers.fc(x, size=1)
    ...
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    loss_val, = exe.run(feed={...}, fetch_list=[loss])
"""
import os as _os

# XLA:CPU runs its optimization-barrier expander BEFORE HLO CSE, which
# silently CSEs jax.checkpoint's rematerialized forward back into the
# original — activation recompute (passes/recompute.py) would be a no-op
# on the CPU proxy and memory_analysis() could never show the savings.
# Keep the barriers alive on CPU (TPU handles them natively); opt out
# with PTPU_KEEP_CSE_BARRIERS=0. Must run before jax initializes.
if _os.environ.get('PTPU_KEEP_CSE_BARRIERS', '1') != '0' \
        and 'cpu' in _os.environ.get('JAX_PLATFORMS', ''):
    _flags = _os.environ.get('XLA_FLAGS', '')
    if 'cse_barrier_expander' not in _flags:
        _os.environ['XLA_FLAGS'] = (
            _flags + ' --xla_disable_hlo_passes=cse_barrier_expander').strip()

from . import ops as _ops  # registers all op lowerings

from .framework import (Program, Block, Operator, Variable, Parameter,
                        default_main_program, default_startup_program,
                        program_guard, name_scope, switch_main_program,
                        switch_startup_program, convert_dtype,
                        CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace)
from .executor import Executor, global_scope, scope_guard, Scope
from .async_executor import AsyncExecutor, DataFeedDesc
from . import recordio
from .backward import append_backward, calc_gradient
from . import layers
from . import initializer
from . import regularizer
from . import clip
from . import optimizer
from . import unique_name
from . import nets
from . import metrics
from . import evaluator
from . import debugger
from . import profiler
from .param_attr import ParamAttr, WeightNormParamAttr
from .data_feeder import DataFeeder
from .initializer import Constant, Uniform, Normal, Xavier, MSRA, Bilinear
from .clip import (ErrorClipByValue, GradientClipByValue, GradientClipByNorm,
                   GradientClipByGlobalNorm, set_gradient_clip)
from .regularizer import L1Decay, L2Decay
from .lod_tensor import (LoDTensor, create_lod_tensor,
                         create_random_int_lodtensor)
from . import io
from .io import (save_vars, save_params, save_persistables, load_vars,
                 load_params, load_persistables, save_inference_model,
                 load_inference_model)
from . import core
from .core.checkpoint import CheckpointManager
from . import passes
from .passes import ProgramVerifyError
from . import contrib
from . import imperative
from . import inference
from .parallel.parallel_executor import ParallelExecutor
from .parallel.compiler import CompiledProgram, BuildStrategy, ExecutionStrategy
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig, \
    memory_optimize, release_memory, InferenceTranspiler

CUDAException = RuntimeError

# persistent compile cache (core/compile_cache.py): when the env knobs
# enable it, initialize at import — the jax persistent-cache tier and the
# compile-event counter must be armed BEFORE the first eager/utility jit
# compiles (rng key derivation fires ahead of the first program dispatch)
from .core import compile_cache as _compile_cache
if _compile_cache.enabled():
    _compile_cache._ensure_ready()

__version__ = '0.1.0'
