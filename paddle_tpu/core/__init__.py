"""Core framework: IR, registry, scope, executor, autodiff, compiler."""
from .backward import append_backward, calc_gradient, gradients  # noqa: F401
from .compiler import (BuildStrategy, CompiledProgram,  # noqa: F401
                       ExecutionStrategy, ShardingStrategy)
from .executor import CPUPlace, CUDAPlace, Executor, Place, TPUPlace  # noqa: F401
from .program import (  # noqa: F401
    Block,
    Operator,
    Parameter,
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    grad_var_name,
    in_dygraph_mode,
    program_guard,
    remat_unit,
    unit,
)
from .registry import get_op, has_op, register_op, registered_ops  # noqa: F401
from .scope import Scope, global_scope, scope_guard  # noqa: F401

# pybind-surface aliases (reference fluid.core — pybind.cc): common names
# scripts touch directly on the core module
from .lod import LoDTensor, LoDTensorArray  # noqa: F401
from .registry import registered_ops as get_all_op_names  # noqa: F401


def is_compiled_with_cuda() -> bool:
    """pybind.cc is_compiled_with_cuda — no CUDA in the TPU build."""
    return False


def is_compiled_with_brpc() -> bool:
    return False


def is_compiled_with_dist() -> bool:
    """Distributed support exists (jax.distributed); reference semantics:
    compiled with the distributed runtime."""
    return True


def op_support_gpu(op_type: str) -> bool:
    """Every registered op lowers through XLA to the device (the
    CPU/GPU-kernel split of op_registry.h doesn't exist here)."""
    return has_op(op_type)
