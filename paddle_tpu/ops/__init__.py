"""TPU-native operator library.

Reference analog: ``paddle/fluid/operators/`` (~505 REGISTER_OPERATOR sites,
SURVEY §2.1). Each module registers pure-JAX implementations into the op
registry; XLA owns kernels, fusion, and layout — there is no per-device kernel
variant dimension (the CPU/CUDA/MKLDNN kernel axis of op_registry.h collapses).

Importing this package registers every op.
"""
from . import (  # noqa: F401
    activation_ops,
    beam_ops,
    collective_ops,
    compare_ops,
    control_flow_ops,
    coverage_ops,
    crf_ops,
    deferred_rows,
    detection_ops,
    framework_ops,
    fused_ops,
    fusion_ops,
    linear_attn_ops,
    math_ops,
    metric_ops,
    misc_ops,
    moe_ops,
    nn_ops,
    optimizer_ops,
    parity_ops,
    pipeline_ops,
    quant_ops,
    reduce_ops,
    rnn_ops,
    sampled_ops,
    sequence_ops,
    ssm_ops,
    tensor_ops,
    vision_ops,
)
from .eager import call as eager_call  # noqa: F401
