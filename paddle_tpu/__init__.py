"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid 1.5 (graph programs, registry autodiff, executors, fleet),
redesigned for XLA/TPU: whole program blocks compile to single XLA
executables; distribution is jax.sharding over device meshes.

The public surface mirrors ``paddle.fluid`` so reference user scripts port by
changing the import. See SURVEY.md at the repo root for the layer map.
"""
from . import ops  # registers all operator lowering rules (import order matters)
from . import initializer, layers, unique_name
from .backward import append_backward, calc_gradient, gradients
from .clip import (GradientClipByGlobalNorm, GradientClipByNorm,
                   GradientClipByValue, set_gradient_clip)
from .executor import (FETCH_LATER, CPUPlace, CUDAPlace, Executor, Scope,
                       TPUPlace, global_scope, scope_guard)
from .framework import (Block, Operator, Parameter, Program, Variable,
                        default_main_program, default_startup_program,
                        in_dygraph_mode, name_scope, program_guard)
from .param_attr import ParamAttr, WeightNormParamAttr
from .parallel import BuildStrategy, CompiledProgram, ExecutionStrategy
from . import contrib
from . import dataset
from . import distributed
from . import dygraph
from . import incubate
from . import inference
from . import io
from . import reader
from .data_feeder import DataFeeder
from .dataset_feed import DatasetFactory
from .reader import DataLoader, PyReader, batch
from . import metrics
from . import optimizer
from . import transpiler
from .transpiler import (DistributeTranspiler, DistributeTranspilerConfig,
                         memory_optimize, release_memory)
from . import monitor
from . import profiler
from . import trace
from . import regularizer
from . import resilience
from . import serving
from . import analysis
from . import aot_cache
from .core import registry as op_registry
from .flags import get_flags, set_flags
from .layers import learning_rate_scheduler  # registers fluid.layers.* decays

__version__ = "0.1.0"

# fluid-style: fluid.data is the recommended input declaration
data = layers.data
