"""LFM2 (mixture-of-experts form): gated short convolutions among
grouped-query attention, gated experts after leading dense layers —
static-graph builder.

Source: the public `config.json` of LiquidAI/LFM2-24B-A2B (`model_type`
`lfm2_moe`). Every layer is pre-norm residual twice over,

    h <- h + operator(RMSNorm(h));  h <- h + ffn(RMSNorm(h))

`layer_types` gives the operator a layer:

  `conv`            a gated short convolution: [B, C, x~] = split3(x W_in);
                    u = B * x~; v_t = sum_j w[:, j] * u_{t-(K-1)+j}
                    (depthwise, causal, K = `conv_L_cache`, no bias, no
                    activation); out = (C * v) W_out
  `full_attention`  grouped-query causal attention: q, k, v = x W_q, x W_k,
                    x W_v; q and k normalised per head (RMSNorm over the
                    head's channels, one learnt weight [head_dim] each), then
                    rotary embedding (rotate-half) on both; out = ctx W_o

and the ffn is a silu-gated MLP, `(silu(x W1) * x W3) W2`, of width
`intermediate_size` in the first `num_dense_layers` layers and `num_experts`
gated experts of width `moe_intermediate_size` after them: sigmoid scores in
float32, the `num_experts_per_tok` largest of score + expert bias chosen (the
bias a buffer without gradient, zero at the start), the chosen scores over
their sum times `routed_scaling_factor` as weights (the published normaliser
adds 1e-6 to the sum of four sigmoids, a relative 5e-7: `parallel/moe.py`'s
`route` guards the division its own way and does not), no shared expert. A
final RMSNorm, and the head is the embedding's table (tied). No
bias anywhere, no dropout.

A chip of an expert-parallel deployment holds a range of each layer's experts
(`experts_held`) and a slice of the vocabulary (`vocab_size` is then the
slice's): the router keeps its full width, pairs on absent experts add
nothing here, the loss is over the slice.

Every layer is one `core.program.unit("blk<i>", remat=True)` whose parts are
sub-units: `op_norm`, `conv/{in_proj,gate_in,filter,gate_out,out_proj}` or
`attn/{qkv,qk_norm,rope,kernel,o}`, `ffn_norm`, `mlp/{gate_up,act,down}` or
`moe/{router,dispatch,experts,combine}` (inside the `moe_ffn` op); then
`final_norm`, `lm_head`, `loss`. The blocks are rematerialised
(`Program.remat_policy = "full"`) and keep what PR 29's rule says is dear to
remake and cheap to hold (at `build_pretrain_program`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import keep, unit
from paddle_tpu.initializer import (ConstantInitializer, NormalInitializer,
                                    UniformInitializer)
from paddle_tpu.models.nemotron_h import record_moe_counters  # noqa: F401
from paddle_tpu.ops.pallas_kernels.flash_attention import KEPT as _ATTN_KEPT
from paddle_tpu.parallel.moe import KEPT as _MOE_KEPT
from paddle_tpu.param_attr import ParamAttr

CONV, ATTENTION = "conv", "full_attention"


def _published_layer_types() -> List[str]:
    return [ATTENTION if i % 4 == 2 else CONV for i in range(40)]


@dataclass
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: List[str] = field(default_factory=_published_layer_types)
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    # attention
    num_heads: int = 32
    num_kv_heads: int = 8
    rope_theta: float = 1e6
    # the short convolution
    conv_L_cache: int = 3
    # experts
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    experts_held: Optional[Tuple[int, int]] = None     # (first, count)
    norm_eps: float = 1e-5
    initializer_range: float = 0.02

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)


def _w(cfg, name):
    return ParamAttr(name=name,
                     initializer=NormalInitializer(0.0, cfg.initializer_range))


def _norm(cfg, x, name):
    return layers.rms_norm(
        x, cfg.norm_eps, param_attr=ParamAttr(
            name=name, initializer=ConstantInitializer(1.0)))


def _head_norm(cfg, x, heads: int, name):
    """RMSNorm over each head's channels of packed heads [B, T, heads * D]
    with one learnt weight [D]: the last-axis norm of [B, T, heads, D]."""
    t, hd = x.shape[1], cfg.head_dim
    y = _norm(cfg, layers.reshape(x, [0, t, heads, hd]), name)
    return layers.reshape(y, [0, t, heads * hd])


def _linear(cfg, x, size, name):
    return layers.fc(x, size, num_flatten_dims=2, param_attr=_w(cfg, name),
                     bias_attr=False)


def short_conv(cfg: Lfm2Config, x, pre: str):
    """The gated short convolution. Its in-projection's [B, T, 3D] result is
    the layer's widest tensor; the two gates and the filter between them are
    elementwise passes over its thirds."""
    d = cfg.hidden_size
    bound = 1.0 / math.sqrt(cfg.conv_L_cache)
    with unit("conv"):
        with unit("in_proj"):
            bcx = _linear(cfg, x, 3 * d, f"{pre}.in_proj.w")
            keep(bcx)
        with unit("gate_in"):
            b, c, xs = layers.split(bcx, 3, dim=2)
            u = layers.elementwise_mul(b, xs)
        with unit("filter"):
            v = layers.causal_conv1d(
                u, cfg.conv_L_cache, bias_attr=False, param_attr=ParamAttr(
                    name=f"{pre}.conv.w",
                    initializer=UniformInitializer(-bound, bound)))
        with unit("gate_out"):
            y = layers.elementwise_mul(c, v)
        with unit("out_proj"):
            return _linear(cfg, y, d, f"{pre}.out_proj.w")


def attention(cfg: Lfm2Config, x, pre: str):
    hd = cfg.head_dim
    q_dim, kv_dim = cfg.num_heads * hd, cfg.num_kv_heads * hd
    with unit("attn"):
        with unit("qkv"):
            qkv = _linear(cfg, x, q_dim + 2 * kv_dim, f"{pre}.qkv.w")
            keep(qkv)
            q, k, v = layers.split(qkv, [q_dim, kv_dim, kv_dim], dim=2)
        with unit("qk_norm"):
            q = _head_norm(cfg, q, cfg.num_heads, f"{pre}.q_norm.w")
            k = _head_norm(cfg, k, cfg.num_kv_heads, f"{pre}.k_norm.w")
        with unit("rope"):
            qk = layers.rotary_embedding(
                layers.concat([q, k], axis=2),
                cfg.num_heads + cfg.num_kv_heads, theta=cfg.rope_theta)
            q, k = layers.split(qk, [q_dim, kv_dim], dim=2)
        with unit("kernel"):
            keep(*_ATTN_KEPT)
            ctx = layers.flash_attention(q, k, v, causal=True,
                                         num_heads=cfg.num_heads,
                                         num_kv_heads=cfg.num_kv_heads)
        with unit("o"):
            return _linear(cfg, ctx, cfg.hidden_size, f"{pre}.o.w")


def dense_mlp(cfg: Lfm2Config, x, pre: str):
    with unit("mlp"):
        with unit("gate_up"):
            gu = _linear(cfg, x, 2 * cfg.intermediate_size,
                         f"{pre}.gate_up.w")
            keep(gu)
        with unit("act"):
            act = layers.swiglu(*layers.split(gu, 2, dim=2))
        with unit("down"):
            return _linear(cfg, act, cfg.hidden_size, f"{pre}.down.w")


def experts(cfg: Lfm2Config, x, pre: str):
    """Returns (out, pairs on each held expert, pairs held)."""
    with unit("moe"):
        out, _, tokens, pairs = layers.moe_ffn(
            x, cfg.num_experts, cfg.moe_intermediate_size,
            k=cfg.num_experts_per_tok, act="silu", gated=True,
            param_attr=_w(cfg, f"{pre}.moe"), bias_attr=False,
            experts_held=cfg.held(), scoring="sigmoid",
            correction_bias=cfg.use_expert_bias,
            norm_topk=cfg.norm_topk_prob,
            routed_scaling=cfg.routed_scaling_factor, return_counts=True)
        keep(*_MOE_KEPT)
        return out, tokens, pairs


def decoder(cfg: Lfm2Config, ids):
    """ids [B, T] -> (hidden [B, T, D] after the final norm, the expert
    layers' counters: [(layer index, TokensPerExpert, PairsHeld)])."""
    with unit("embed"):
        x = layers.embedding(ids, [cfg.vocab_size, cfg.hidden_size],
                             param_attr=_w(cfg, "embed.w"))
    counters = []
    for i, kind in enumerate(cfg.layer_types):
        pre = f"blk{i}"
        if kind not in (CONV, ATTENTION):
            raise ValueError(f"layer_types: unknown operator {kind!r} at {i}")
        with unit(pre, remat=True):
            with unit("op_norm"):
                h = _norm(cfg, x, f"{pre}.op_norm.w")
            operator = short_conv if kind == CONV else attention
            x = layers.elementwise_add(x, operator(cfg, h, pre))
            with unit("ffn_norm"):
                h = _norm(cfg, x, f"{pre}.ffn_norm.w")
            if i < cfg.num_dense_layers:
                out = dense_mlp(cfg, h, pre)
            else:
                out, tokens, pairs = experts(cfg, h, pre)
                counters.append((i, tokens, pairs))
            x = layers.elementwise_add(x, out)
    with unit("final_norm"):
        x = _norm(cfg, x, "final_norm.w")
    return x, counters


def build_pretrain_program(cfg: Lfm2Config, batch_size: int, seq_len: int,
                           optimizer_factory=None):
    """(main, startup, feed names, loss, counters) of one next-token
    pretraining step: feeds `ids` and `labels` [B, T] (the caller shifts),
    the loss the mean over all positions of the cross entropy of the tied
    head, chunked (`linear_softmax_with_cross_entropy` reading the
    embedding's table: one parameter, one Adam slot, one gradient that is
    the lookup's rows plus the projection's). `counters` lists, per expert
    layer, (layer index, TokensPerExpert, PairsHeld): fetch them where the
    loss is fetched and hand them to `record_moe_counters`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", [seq_len], dtype="int64")
        labels = layers.data("labels", [seq_len, 1], dtype="int64")
        hidden, counters = decoder(cfg, ids)
        with unit("lm_head"):
            per_token = layers.linear_softmax_with_cross_entropy(
                hidden, labels, cfg.vocab_size, param_attr=_w(cfg, "embed.w"),
                bias_attr=False, tied_table=True)
        with unit("loss"):
            loss = layers.reduce_mean(per_token)
        if optimizer_factory is not None:
            optimizer_factory().minimize(loss)
    # Each layer is recomputed in the backward pass from its input (the
    # float32 residual stream) and from what it keeps. PR 29's rule: keep
    # what costs far more operations a byte held than the chip's ridge (240
    # on a v5e). A product 2,048 deep is 2,048 operations a byte of its bf16
    # result: the convolution's in-projection (12 KB a token), q/k/v (6 KB),
    # the dense MLP's gate and up (46 KB, in one layer only), the router's
    # logits; the attention kernel's forward about 8,000 at T 8,192 for `out`
    # and `lse`; the plan a sort for a few integers. The norms, the two
    # gates, the filter, the rotation, the activation and the splits cost a
    # handful and are made again, and the experts' tiles make their own
    # hidden halves again whatever is kept.
    main.remat_policy = "full"
    return main, startup, ["ids", "labels"], loss, counters


def param_count(cfg: Lfm2Config) -> int:
    d, hd = cfg.hidden_size, cfg.head_dim
    kv = cfg.num_kv_heads * hd
    conv = d * 3 * d + d * cfg.conv_L_cache + d * d
    attn = d * (d + 2 * kv) + 2 * hd + d * d
    mlp = 3 * d * cfg.intermediate_size
    moe = (d * cfg.num_experts + (cfg.num_experts if cfg.use_expert_bias
                                  else 0)
           + cfg.held()[1] * 3 * d * cfg.moe_intermediate_size)
    total = cfg.vocab_size * d + d
    for i, kind in enumerate(cfg.layer_types):
        total += 2 * d + (conv if kind == CONV else attn)
        total += mlp if i < cfg.num_dense_layers else moe
    return total
