"""Op lists for mixed precision (reference contrib/mixed_precision/
fp16_lists.py). On TPU the low-precision dtype is bfloat16 by default."""
from __future__ import annotations

# ops whose inputs are cast to the compute dtype (MXU-bound).
# softmax_with_cross_entropy is here because its kernel reduces in f32
# internally (nn_ops._hard_label_ce) — casting the [B,T,vocab] logits input
# keeps the saved residual low-precision (2 GB instead of 4 GB on the
# BERT-base MLM head) with no f32 math lost.
WHITE_LIST = {"conv2d", "conv3d", "depthwise_conv2d", "conv2d_transpose",
              "matmul", "mul", "fused_fc", "fused_elemwise_activation",
              "flash_attention", "softmax_with_cross_entropy"}
# ops kept in float32 (numerically sensitive). softmax_with_cross_entropy is
# deliberately NOT here: its kernel takes low-precision logits and does the
# reductions in f32 internally (nn_ops._hard_label_ce) — black-listing it
# would materialize a full-vocab f32 logits copy just to feed it.
# batch_norm is gray (not listed): its kernel keeps x in the native dtype
# and does the statistics in f32 internally — black-listing it would bounce
# a bf16 conv trunk through f32 HBM at every layer.
# layer_norm is gray (not listed): its kernel takes bf16 activations and
# does the statistics in f32 internally (nn_ops._layer_norm) — black-listing
# it would bounce the residual stream through f32 HBM at every layer.
# linear_softmax_with_cross_entropy is gray (not listed): it multiplies in
# the dtype its activations arrive in (bf16 after a bf16 trunk) with float32
# accumulation, keeps the logits, the softmax and the loss in float32 and
# sums dW / dBias in float32 straight into the float32 masters
# (nn_ops._linear_ce) — white-listing it would round Bias and both
# gradients to bf16, black-listing it would run the three products in f32.
# rms_norm is gray (not listed), like layer_norm: activations in whatever
# dtype, the mean square and the gate's silu in float32, the input dtype
# back. relu2 is gray: elementwise, in the dtype it is given.
# causal_conv1d and ssd_scan are gray: they take the bf16 activations a bf16
# projection hands them and keep in float32 what needs it (the convolution's
# sum; the scan's time steps, decays and states, ops/ssm_ops.py) —
# white-listing the scan would round A_log, dt_bias and D to bf16,
# black-listing it would run its chunk products in float32.
# moe_ffn is gray: its router runs in float32 at full matmul precision
# whatever arrives (a near-tie between two experts flips on rounding), its
# expert products take the activations' dtype with float32 accumulation, and
# the experts' weight gradients are summed in float32 (parallel/moe.py).
# rotary_embedding and swiglu are gray: bf16 in and out beside the bf16
# products they sit between; inside, float32: the angles, the tables of
# cosines and signed sines and the multiply-adds `x C + partner(x) S` with
# one rounding at the end (forward, and the op's own backward rule on the
# bf16 cotangent: ops/nn_ops.py `_rope_turn`), the silu and the product.
# gated_delta_rule is gray, as ssd_scan: bf16 q, k, v and raw gate values in
# and bf16 out beside the bf16 products they sit between; inside, float32:
# the l2 normalisation, the gate's softplus, the cumulative decays, the
# triangular system and the states (ops/linear_attn_ops.py).
# loop_exit_gate and loop_exit_loss are gray and float32 inside whatever
# arrives: the gate is a full-precision product (its output weights the
# loss) and black-listing them would only add a cast of the states they read.
BLACK_LIST = {"cross_entropy", "mean",
              "reduce_mean", "softmax", "sum",
              "exp", "log", "rsqrt", "sqrt"}


class AutoMixedPrecisionLists:
    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = set(WHITE_LIST)
        self.black_list = set(BLACK_LIST)
        if custom_white_list:
            self.white_list |= set(custom_white_list)
        if custom_black_list:
            self.black_list |= set(custom_black_list)
        self.white_list -= self.black_list
