"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid 1.5 (reference: /root/reference), built on JAX/XLA/Pallas.

The top-level module doubles as the `fluid` namespace: `import paddle_tpu as
fluid` makes reference recipes (layers/executor/optimizer/io) run unchanged —
but everything underneath is a ground-up TPU design (see SURVEY.md §1):
whole-program XLA compilation, jax.grad autodiff, SPMD parallelism over
jax.sharding meshes, Pallas kernels for the hot paths.
"""

from . import observability
from . import initializer
from .core import (framework, unique_name)
from .core.framework import (Program, Variable, Parameter, program_guard,
                             name_scope, default_main_program,
                             default_startup_program, in_dygraph_mode)
from .core.place import (cuda_pinned_places,
                         CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace,
                         cpu_places, cuda_places, tpu_places,
                         is_compiled_with_cuda, is_compiled_with_tpu)
from .core.executor import (Executor, FetchHandle, Scope, global_scope,
                            scope_guard)
from .core.bucketing import FeedBucketer
from .core.lod import (LoDTensor, create_lod_tensor,
                       create_random_int_lodtensor)
from .core.backward import append_backward, gradients
from .core.param_attr import ParamAttr, WeightNormParamAttr
from .core.data_feeder import DataFeeder
from .core.compiler import (CompiledProgram, ParallelExecutor, BuildStrategy,
                            ExecutionStrategy)
from . import layers
from . import nets
from .layers.io import data  # fluid.data-style (but with batch dim implicit off)
from . import optimizer
from .optimizer import clip
from .optimizer import regularizer
from . import metrics
from . import average
from . import evaluator
from . import net_drawer
from . import contrib
from . import incubate
from . import communicator
from .communicator import Communicator
from . import io
from .io.state import (save_params, save_persistables, save_vars, load_params,
                       load_persistables, load_vars)
from .io.inference_io import save_inference_model, load_inference_model
from .io.dataset import (DatasetFactory, InMemoryDataset, QueueDataset,
                         FileInstantDataset, BoxPSDataset, DataFeedDesc)
from . import dataset
from . import reader
from . import dygraph
from . import parallel
# fluid exposes the transpiler surface at top level (ref fluid/__init__.py
# pulling transpiler.__all__); same names, mesh-first implementations
from . import transpiler
from .transpiler import (DistributeTranspiler,
                         DistributeTranspilerConfig,
                         memory_optimize, release_memory)
from . import profiler
from . import amp
from . import robustness
from . import models
from . import utils
from .utils import install_check   # fluid.install_check.run_check() parity
from . import inference

# fluid-compat: `fluid.data` in 2.x has no implicit batch dim. Keep both:
data = layers.io.fluid_data


def embedding(*args, **kwargs):
    return layers.embedding(*args, **kwargs)


def one_hot(*args, **kwargs):
    return layers.one_hot(*args, **kwargs)


__version__ = "0.1.0"
