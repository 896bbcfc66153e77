"""paddle_tpu — a TPU-native deep learning framework.

A ground-up re-design of the capabilities of PaddlePaddle Fluid (reference:
/root/reference, see SURVEY.md) in the TPU idiom: programs trace to XLA,
parallelism is GSPMD sharding over `jax.sharding.Mesh`, hot kernels are
Pallas, collectives ride ICI.

API shape follows fluid for migration friendliness::

    import paddle_tpu as fluid
    x = fluid.layers.data("x", [784])
    y = fluid.layers.fc(x, 10, act="softmax")
    ...
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed={...}, fetch_list=[loss])
"""
import time as _time

_IMPORT_BEGAN = _time.perf_counter()  # the set-up account's `import` phase

from . import initializer  # noqa: F401
from . import ops  # registers all ops  # noqa: F401
from . import layers  # noqa: F401
from . import nets  # noqa: F401
from . import clip  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import contrib  # noqa: F401
from . import dataio  # noqa: F401
from .dataio import DeviceLoader, FetchHandle  # noqa: F401
from . import debugger  # noqa: F401
from . import dygraph  # noqa: F401
from . import io  # noqa: F401
from . import ir  # noqa: F401
from . import inference  # noqa: F401
from . import metrics  # noqa: F401
from . import faults  # noqa: F401
from . import observability  # noqa: F401
from . import parallel  # noqa: F401
from . import planner  # noqa: F401
from . import ps  # noqa: F401
from . import profiler  # noqa: F401
from . import serving  # noqa: F401
from . import reader as py_reader_module  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from .dataset import DatasetFactory  # noqa: F401
from .flags import get_flags, set_flags  # noqa: F401
from .layers import learning_rate_scheduler  # noqa: F401
from .reader import PyReader  # noqa: F401
from .core import (  # noqa: F401
    Block,
    BuildStrategy,
    CompiledProgram,
    CPUPlace,
    CUDAPlace,
    ExecutionStrategy,
    Executor,
    Operator,
    Parameter,
    Place,
    Program,
    Scope,
    ShardingStrategy,
    TPUPlace,
    Variable,
    append_backward,
    calc_gradient,
    default_main_program,
    default_startup_program,
    global_scope,
    gradients,
    in_dygraph_mode,
    program_guard,
    remat_unit,
    unit,
    scope_guard,
)
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .core import unique_name  # noqa: F401
from . import average  # noqa: F401
from . import evaluator  # noqa: F401
from . import data_feed_desc  # noqa: F401
from .data_feed_desc import DataFeedDesc  # noqa: F401
from . import distribute_lookup_table  # noqa: F401
from . import dygraph_grad_clip  # noqa: F401
from . import incubate  # noqa: F401
from . import inferencer  # noqa: F401
from . import install_check  # noqa: F401
from . import compiler  # noqa: F401
from . import parallel_executor  # noqa: F401
from .parallel_executor import ParallelExecutor  # noqa: F401
from . import trainer_desc  # noqa: F401
from .core import executor  # noqa: F401
from .core import program as framework  # noqa: F401
from .average import WeightedAverage  # noqa: F401
from .evaluator import Evaluator  # noqa: F401
from . import net_drawer  # noqa: F401

# register the aliased modules so `from paddle_tpu.framework import ...`
# (the reference's common import form) resolves, not just attribute access
import sys as _sys

_sys.modules[__name__ + ".framework"] = framework
_sys.modules[__name__ + ".executor"] = executor
del _sys
from . import data_generator  # noqa: F401
from . import transpiler  # noqa: F401
from .core.lod import (  # noqa: F401
    LoDTensor,
    LoDTensorArray,
    create_lod_tensor,
    create_random_int_lodtensor,
)
from .layers.math_op_patch import monkey_patch_variable  # noqa: F401
from .parallel.fleet import fleet  # noqa: F401
from .transpiler import (  # noqa: F401
    DistributeTranspiler,
    DistributeTranspilerConfig,
    memory_optimize,
    release_memory,
)


def CUDAPinnedPlace():
    """place.h CUDAPinnedPlace parity — host staging is XLA's job here; maps
    to the CPU place."""
    return CPUPlace()


_Scope = Scope  # pybind alias parity (pybind.cc Scope binding)

__version__ = "0.1.0"


def _late_imports():
    """Attach subpackages that depend on the core being importable."""
    from . import backward  # noqa: F401


class backward:  # namespace parity: fluid.backward.append_backward
    from .core.backward import append_backward, calc_gradient, gradients

    append_backward = staticmethod(append_backward)
    calc_gradient = staticmethod(calc_gradient)
    gradients = staticmethod(gradients)

observability.setup_account.note_import(_IMPORT_BEGAN)
del _time
