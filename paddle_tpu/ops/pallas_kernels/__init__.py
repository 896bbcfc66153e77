"""Pallas TPU kernels for the hot ops.

No reference analog as such — the reference's hot-op strategy is hand-written
CUDA (e.g. softmax_cudnn, fused attention via operators/fused/) plus the x86
JIT library (operators/jit/). On TPU the equivalent of "hand kernel where the
compiler isn't enough" is Pallas; everything else stays plain JAX and lets XLA
fuse. The dispatch idea of operators/jit (pick best impl at runtime) survives
as: pallas kernel on TPU when its constraints hold, blockwise-JAX fallback
everywhere else.
"""
from .flash_attention import flash_attention  # noqa: F401
