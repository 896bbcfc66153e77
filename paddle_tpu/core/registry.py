"""Op registry: op type -> JAX implementation + metadata.

Reference analog: ``paddle/fluid/framework/op_registry.h:199``
(REGISTER_OPERATOR / REGISTER_OP_CPU_KERNEL / REGISTER_OP_CUDA_KERNEL) and the
OpKernelType dispatch in operator.cc:970.

TPU-native redesign: an op has ONE implementation — a pure JAX function — and
XLA owns device lowering, so the (place, layout, library) kernel-key machinery
disappears. Gradients are not hand-registered per op (reference
grad_op_desc_maker.h); instead the executor records a jax.vjp tape for every
differentiable op, which is the functional-idiom equivalent of GradOpMaker.

Implementation contract::

    @register_op("relu")                      # differentiable by default
    def relu(ctx, inputs, attrs):
        (x,) = inputs["X"]
        return {"Out": [jax.nn.relu(x)]}

- `inputs`: dict slot -> list of concrete jax values (tracers under jit).
- `attrs`: static attr dict from the OpDesc.
- `ctx`:  ExecContext — rng key derivation, is_test flag, block lowering for
  control-flow ops, mesh/axis info for collective ops.
- returns dict slot -> list of values matching the op's output slots.

Ops marked differentiable=False (optimizer updates, metrics, IO, random
number generation, integer-output ops) are executed outside the vjp tape.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

OpImpl = Callable[..., Dict[str, List[Any]]]


class OpDef:
    __slots__ = ("type", "fn", "differentiable", "nondiff_inputs",
                 "mutable_persistables", "grad_fn", "own_remat")

    def __init__(self, type: str, fn: OpImpl, differentiable: bool = True,
                 nondiff_inputs: Optional[List[str]] = None, grad_fn=None,
                 own_remat: bool = False):
        self.type = type
        self.fn = fn
        self.differentiable = differentiable
        # input slots that never receive gradients (e.g. integer indices)
        self.nondiff_inputs = set(nondiff_inputs or [])
        # hand-written gradient (GradOpMaker analog) for ops whose cotangent
        # is not a dense array — e.g. lookup_table's SelectedRows rows.
        # Signature: grad_fn(ctx, inputs, attrs, outputs, out_cots) ->
        # {slot: [cotangent or None, ...]}. May return None to fall back to
        # jax.vjp for this invocation (attr-dependent sparsity).
        self.grad_fn = grad_fn
        # the op is its own rematerialisation: its gradient rule keeps only
        # the op's inputs and O(rows) vectors and recomputes the rest
        # itself, so a jax.checkpoint around it would free nothing and run
        # its forward rule a second time in the backward pass. The executor
        # leaves such an op unwrapped under a per-op remat policy.
        self.own_remat = own_remat


_REGISTRY: Dict[str, OpDef] = {}


def register_op(type: str, differentiable: bool = True, nondiff_inputs=None,
                grad_fn=None, own_remat: bool = False):
    def deco(fn: OpImpl):
        if type in _REGISTRY:
            raise ValueError(f"op {type!r} registered twice")
        _REGISTRY[type] = OpDef(type, fn, differentiable, nondiff_inputs,
                                grad_fn, own_remat)
        return fn

    return deco


def get_op(type: str) -> OpDef:
    if type not in _REGISTRY:
        raise KeyError(
            f"op {type!r} has no registered TPU implementation "
            f"({len(_REGISTRY)} ops registered)")
    return _REGISTRY[type]


def has_op(type: str) -> bool:
    return type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)
