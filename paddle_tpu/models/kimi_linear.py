"""Kimi Linear (moonshotai, `model_type` `kimi_linear`): Kimi Delta Attention
3 : 1 with latent attention that carries no position, gated experts beside a
shared expert after a leading dense layer — static-graph builder.

Source: the public `config.json` of moonshotai/Kimi-Linear-48B-A3B-Instruct;
the Kimi Linear report (arXiv:2510.26692) section 3 for the delta rule with a
decay per channel. Every layer is pre-norm residual twice over, all norms
RMSNorm with one learnt weight, no bias anywhere but `dt_bias`:

    h <- h + mixer(RMSNorm(h));  h <- h + ffn(RMSNorm(h))

Layers are counted from 1 where the published lists count them
(`kda_layers`, `full_attn_layers`); units, parameters and the counters' layer
indices count from 0, as every builder here.

  KDA               q, k, v = silu(conv(x W_q | W_k | W_v)), H heads of
                    `head_dim`, depthwise causal filters of
                    `short_conv_kernel_size` taps; q and k divided by
                    max(their norm, 1e-6) a head and position, in float32
                    (inside the rule's op);
                    g = -exp(A_log[h]) softplus((x W_fa) W_fb + dt_bias), a
                    log-decay a CHANNEL, float32; beta = sigmoid(x W_b) a
                    head, float32;
                    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                          + beta_t k_t v_t^T, a [head_dim, head_dim] state a
                    head; o_t = head_dim^-1/2 S_t^T q_t
                    (`layers.gated_delta_rule`, ops/linear_attn_ops.py);
                    y = (RMSNorm_head(o) * sigmoid((x W_ga) W_gb)) W_o, the
                    norm's one [head_dim] weight for all heads
  latent attention  `models/joyai_flash.py` `latent_attention` with
                    `q_lora_rank` None (q straight from x) and
                    `mla_use_nope` (nothing is rotated; the one shared
                    64-wide key head is kept): the KDA layers carry the order
  ffn, dense        (silu(x W1) * x W3) W2, the first `first_k_dense_replace`
                    layers
  ffn, experts      sigmoid scores in float32 over all `num_experts`, the
                    `num_experts_per_token` largest of score + bias chosen
                    (one group: no group limit; the bias a buffer without
                    gradient), weights the chosen scores over their sum
                    (`moe_renormalize`) times `routed_scaling_factor`; gated
                    experts; plus `num_shared_experts` shared, unweighted

then a final RMSNorm and an untied head. No rotation and no position
embedding anywhere.

A chip of an expert-parallel deployment holds a range of each layer's experts
(`experts_held`) and a slice of the vocabulary (`vocab_size` is then the
slice's): the router keeps its full width, pairs on absent experts add
nothing here, the lookup and the loss are over the slice.

Every layer is one `core.program.unit("blk<i>", remat=True)` whose parts are
sub-units: `op_norm`, `kda/{q,k,v,conv,decay,beta,rule,out_gate,out_norm,o}`
(`decay` and `out_gate` the two low-rank gates' products; the l2
normalisation and the decay's softplus are inside `rule`, the output gate's
sigmoid inside `out_norm`) or `attn/{q_b,kv_a,kv_norm,kv_b,assemble,kernel,o}`, `ffn_norm`,
`mlp/{gate_up,act,down}` or `moe/{router,dispatch,experts,combine,shared}`;
then `final_norm`, `lm_head`, `loss`.

What the builders share (`_w`, `_linear`, `_norm`, the gated MLP, latent
attention, the experts beside a shared one) is JoyAI-Flash's, taken by name
and not written again.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import keep, unit
from paddle_tpu.initializer import (ConstantInitializer,
                                    NumpyArrayInitializer)
from paddle_tpu.models.joyai_flash import (_linear, _norm, _w, dense_mlp,
                                           experts, latent_attention)
from paddle_tpu.models.nemotron_h import record_moe_counters
from paddle_tpu.param_attr import ParamAttr

L2_EPS = 1e-6


@dataclass
class KimiLinearConfig:
    """The published keys of Kimi-Linear-48B-A3B (defaults), `experts_held`,
    and what the row does not fix (`assumed` in the benchmark's file)."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216
    # Kimi Delta Attention (`linear_attn_config`; lists count from 1)
    kda_layers: List[int] = field(default_factory=lambda: [
        i for i in range(1, 28) if i % 4 and i != 27])
    full_attn_layers: List[int] = field(default_factory=lambda: [
        4, 8, 12, 16, 20, 24, 27])
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_gate_rank: int = 128      # the two low-rank gates' inner width
    kda_chunk: int = 64
    a_range: Tuple[float, float] = (1.0, 16.0)
    dt_range: Tuple[float, float] = (1e-3, 1e-1)
    # latent attention without position
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    # experts
    num_experts: int = 256
    num_experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    experts_held: Optional[Tuple[int, int]] = None     # (first, count)
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02

    # the names `models/joyai_flash.py`'s shared layers read
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    @property
    def n_shared_experts(self) -> int:
        return self.num_shared_experts

    @property
    def norm_topk_prob(self) -> bool:
        return self.moe_renormalize

    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def is_kda(self, layer: int) -> bool:
        """Layer `layer`, counted from 0, against the published lists."""
        kda = layer + 1 in self.kda_layers
        if kda == (layer + 1 in self.full_attn_layers):
            raise ValueError(f"kimi_linear: layer {layer + 1} is in both or "
                             "neither of kda_layers and full_attn_layers")
        return kda


def kda_defaults(cfg: KimiLinearConfig):
    """The per-head and per-channel parameters' own initial values, spread
    evenly so that they need no generator (as `nemotron_h.mamba_defaults`):
    A = exp(A_log) over `a_range` head by head, and dt_bias the inverse
    softplus of steps spread evenly in the log over `dt_range`, channel by
    channel."""
    h, wide = cfg.kda_num_heads, cfg.kda_num_heads * cfg.kda_head_dim
    a_log = np.log(np.linspace(*cfg.a_range, h))
    dt = np.exp(np.linspace(math.log(cfg.dt_range[0]),
                            math.log(cfg.dt_range[1]), wide))
    dt_bias = dt + np.log(-np.expm1(-dt))
    return a_log.astype(np.float32), dt_bias.astype(np.float32)


def kda_attention(cfg: KimiLinearConfig, x, pre: str):
    """x [B, T, D] -> ([B, T, D], the rule's decay floor). The three
    projections' results before the filters and the narrow results of the
    two gates' first products and of beta's stay for the backward pass; the
    filters, the gates, the rule and the norm are made again.

    The l2 normalisation of q and k and the decay gate from its bias on
    (softplus, exp(A_log), the float32 log-decay) are the rule's own
    (`layers.gated_delta_rule`'s `qk_l2norm`, `a_log`, `dt_bias`): made
    there a group of chunks at a time, forward and backward, they are never
    held for a whole layer; the output gate's sigmoid and product are the
    norm's (`rms_norm`'s `gate_after`)."""
    t, nh, hd = x.shape[1], cfg.kda_num_heads, cfg.kda_head_dim
    wide = nh * hd
    heads = lambda y: layers.reshape(y, [0, t, nh, hd])
    a_log, dt_bias = kda_defaults(cfg)
    with unit("kda"):
        raw = {}
        for n in "qkv":
            with unit(n):
                raw[n] = _linear(cfg, x, wide, f"{pre}.{n}.w")
                keep(raw[n])
        with unit("conv"):
            q, k, v = (heads(layers.causal_conv1d(
                raw[n], cfg.short_conv_kernel_size, act="silu",
                param_attr=_w(cfg, f"{pre}.{n}_conv.w"), bias_attr=False))
                for n in "qkv")
        with unit("decay"):
            f_a = _linear(cfg, x, cfg.kda_gate_rank, f"{pre}.f_a.w")
            keep(f_a)
            f_b = heads(_linear(cfg, f_a, wide, f"{pre}.f_b.w"))
        with unit("beta"):
            b = _linear(cfg, x, nh, f"{pre}.beta.w")
            keep(b)
            beta = layers.sigmoid(layers.cast(b, "float32"))
        with unit("rule"):
            o, floor = layers.gated_delta_rule(
                q, k, v, f_b, beta, chunk=cfg.kda_chunk,
                return_decay_floor=True, qk_l2norm=L2_EPS,
                a_log=layers.create_parameter(
                    [nh], "float32", attr=ParamAttr(
                        name=f"{pre}.A_log",
                        initializer=NumpyArrayInitializer(a_log))),
                dt_bias=layers.create_parameter(
                    [wide], "float32", attr=ParamAttr(
                        name=f"{pre}.dt_bias",
                        initializer=NumpyArrayInitializer(dt_bias))))
        with unit("out_gate"):
            g_a = _linear(cfg, x, cfg.kda_gate_rank, f"{pre}.g_a.w")
            keep(g_a)
            g_b = heads(_linear(cfg, g_a, wide, f"{pre}.g_b.w"))
        with unit("out_norm"):
            y = layers.rms_norm(
                o, cfg.rms_norm_eps, gate=g_b, gate_after="sigmoid",
                param_attr=ParamAttr(name=f"{pre}.o_norm.w",
                                     initializer=ConstantInitializer(1.0)))
        with unit("o"):
            return _linear(cfg, layers.reshape(y, [0, t, wide]),
                           cfg.hidden_size, f"{pre}.o.w"), floor


def decoder(cfg: KimiLinearConfig, ids):
    """ids [B, T] -> (hidden [B, T, D] after the final norm, the expert
    layers' counters [(layer index, TokensPerExpert, PairsHeld)], the KDA
    layers' [(layer index, DecayFloor)])."""
    with unit("embed"):
        x = layers.embedding(ids, [cfg.vocab_size, cfg.hidden_size],
                             param_attr=_w(cfg, "embed.w"))
    counters, floors = [], []
    for i in range(cfg.num_hidden_layers):
        pre = f"blk{i}"
        with unit(pre, remat=True):
            with unit("op_norm"):
                h = _norm(cfg, x, f"{pre}.op_norm.w")
            if cfg.is_kda(i):
                out, floor = kda_attention(cfg, h, pre)
                floors.append((i, floor))
            else:
                out = latent_attention(cfg, h, pre)
            x = layers.elementwise_add(x, out)
            with unit("ffn_norm"):
                h = _norm(cfg, x, f"{pre}.ffn_norm.w")
            if i < cfg.first_k_dense_replace:
                out = dense_mlp(cfg, h, pre)
            else:
                out, tokens, pairs = experts(cfg, h, pre)
                counters.append((i, tokens, pairs))
            x = layers.elementwise_add(x, out)
    with unit("final_norm"):
        x = _norm(cfg, x, "final_norm.w")
    return x, counters, floors


def build_pretrain_program(cfg: KimiLinearConfig, batch_size: int,
                           seq_len: int, optimizer_factory=None):
    """(main, startup, feed names, loss, counters, floors) of one next-token
    pretraining step: feeds `ids` and `labels` [B, T] (the caller shifts),
    the loss the mean over all positions of the cross entropy of the untied
    head, chunked (`linear_softmax_with_cross_entropy`). `counters` lists,
    per expert layer, (layer index, TokensPerExpert, PairsHeld) and `floors`,
    per KDA layer, (layer index, DecayFloor): fetch them where the loss is
    fetched and hand them to `record_counters`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", [seq_len], dtype="int64")
        labels = layers.data("labels", [seq_len, 1], dtype="int64")
        hidden, counters, floors = decoder(cfg, ids)
        with unit("lm_head"):
            per_token = layers.linear_softmax_with_cross_entropy(
                hidden, labels, cfg.vocab_size,
                param_attr=_w(cfg, "lm_head.w"), bias_attr=False)
        with unit("loss"):
            loss = layers.reduce_mean(per_token)
        if optimizer_factory is not None:
            optimizer_factory().minimize(loss)
    # Each layer is recomputed in the backward pass from its input (the
    # float32 residual stream) and from what it keeps. PR 29's rule: keep
    # what costs far more operations a byte held than the chip's ridge (240
    # on a v5e) and is small. A KDA layer keeps its three projections'
    # results BEFORE the filters (products 2,304 deep, 24 KB a token
    # together: the filters' results would be as large and save 8
    # operations a byte, so the filters are made again and their inputs
    # serve their own backward pass too) and the narrow results of the two
    # gates' first products and of beta's (2,304 deep, 0.6 KB a token). The
    # gates' second products are 128 deep (under the ridge), the rule's `o`
    # and chunk states 0.65 GiB a layer at b2 x T8192 for a forward pass of
    # a few milliseconds: made again, with the norms and the activations.
    # The latent-attention layer, the dense MLP and the experts keep what
    # JoyAI-Flash's keep.
    main.remat_policy = "full"
    return main, startup, ["ids", "labels"], loss, counters, floors


def record_counters(counters, floors, fetched, tokens_per_step: int, k: int):
    """Set the `moe/*` gauges (`nemotron_h.record_moe_counters`) from the
    fetched values of `counters`, and `kda/decay_floor` from the fetched
    `floors` that follow them: the most negative cumulative log-decay any
    chunk of any KDA layer reached in that step."""
    from ..observability import get_registry
    moe = fetched[:2 * len(counters)]
    record_moe_counters(counters, moe, tokens_per_step, k)
    if floors:
        get_registry().gauge("kda/decay_floor").set(
            min(float(np.asarray(f)) for f in fetched[len(moe):]))


def param_count(cfg: KimiLinearConfig, touched: bool = False) -> int:
    """Trained parameters (the routers' bias buffers are not); with
    `touched` the ones a token passes through, as a model card counts them:
    `num_experts_per_token` of an expert layer's routed experts, and the
    embedding's lookup not counted."""
    d = cfg.hidden_size
    wide, rank = cfg.kda_num_heads * cfg.kda_head_dim, cfg.kda_gate_rank
    kda = (3 * d * wide + 3 * wide * cfg.short_conv_kernel_size
           + d * rank + rank * wide + wide + cfg.kda_num_heads   # decay
           + d * cfg.kda_num_heads                               # beta
           + d * rank + rank * wide + cfg.kda_head_dim           # gate, norm
           + wide * d)
    nh = cfg.num_attention_heads
    mla = (d * nh * cfg.qk_head_dim
           + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) + cfg.kv_lora_rank
           + cfg.kv_lora_rank * nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)
           + nh * cfg.v_head_dim * d)
    expert = 3 * d * cfg.moe_intermediate_size
    routed = cfg.num_experts_per_token if touched else cfg.held()[1]
    moe = d * cfg.num_experts + (routed + cfg.num_shared_experts) * expert
    total = (1 if touched else 2) * cfg.vocab_size * d + d
    for i in range(cfg.num_hidden_layers):
        total += 2 * d + (kda if cfg.is_kda(i) else mla)
        total += (3 * d * cfg.intermediate_size
                  if i < cfg.first_k_dense_replace else moe)
    return total
