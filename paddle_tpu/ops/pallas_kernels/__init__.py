"""Pallas TPU kernels for the hot ops.

No reference analog as such — the reference's hot-op strategy is hand-written
CUDA (e.g. softmax_cudnn, fused attention via operators/fused/) plus the x86
JIT library (operators/jit/). On TPU the equivalent of "hand kernel where the
compiler isn't enough" is Pallas; everything else stays plain JAX and lets XLA
fuse. The dispatch idea of operators/jit (pick best impl at runtime) survives
as: pallas kernel on TPU when its constraints hold, blockwise-JAX fallback
everywhere else.

Modules: `flash_attention` (dense, grouped-query and block-sparse packed
attention, forward and backward), `fused_bn` (batch norm + activation, 1x1
conv + batch norm), `ssd_scan` (Mamba-2's chunked state-space scan, forward
and backward; imported by ops/ssm_ops.py when an op is lowered, not here),
`grouped_ffn` (the experts' grouped product of parallel/moe.py, forward and
backward; imported there where the product's form is picked, not here),
`kda_chunk` (Kimi Delta Attention's chunked gated delta rule, forward and
backward; imported by ops/linear_attn_ops.py when an op is lowered, not
here).
"""
from .flash_attention import flash_attention  # noqa: F401
