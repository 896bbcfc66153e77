"""Nemotron-H: a hybrid Mamba-2 / mixture-of-experts / attention decoder —
static-graph builder.

Source: the public `config.json` of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B
(`model_type` `nemotron_h`). `hybrid_override_pattern` gives one mixer a
block: `M` a Mamba-2 mixer, `E` a mixture-of-experts MLP (routed experts plus
a shared expert, squared ReLU), `*` grouped-query causal attention with no
position embedding. Every block is pre-norm residual,
`x <- x + mixer(RMSNorm(x))`; a final RMSNorm and an untied output matrix
follow. No dropout, no bias but the convolution's.

A chip of an expert-parallel deployment holds a range of each layer's experts
(`experts_held`) and a slice of the vocabulary (`vocab_size` is then the
slice's): the router keeps its full width, pairs on absent experts add
nothing here, the loss is over the slice.

Every block is one `core.program.unit("blk<i>.<M|E|A>", remat=True)` whose
parts are sub-units (`mamba/in_proj`, `mamba/conv`, `mamba/ssd`,
`mamba/norm`, `mamba/out_proj`; `attn`; `moe/router`, `moe/dispatch`,
`moe/experts`, `moe/combine` inside the `moe_ffn` op and `moe/shared`), so a
device trace names the part every operation belongs to. The program asks for
its blocks to be rematerialised (`Program.remat_policy = "full"`): a block's
forward is made again in the backward pass, which is what lets 16k tokens of
a 9-block cut train beside 16 bytes a parameter of state. A block keeps its
input and the few values that are dear to remake and cheap to hold
(`core.program.keep`; the rule is at `build_pretrain_program`): the
in-projection's result, the q/k/v product with the attention kernel's
outputs, the shared expert's first product, the routing and its plan.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import keep, unit
from paddle_tpu.initializer import (ConstantInitializer, NormalInitializer,
                                    NumpyArrayInitializer)
from paddle_tpu.ops.pallas_kernels.flash_attention import KEPT as _ATTN_KEPT
from paddle_tpu.parallel.moe import KEPT as _MOE_KEPT
from paddle_tpu.param_attr import ParamAttr

# a block's kind in the pattern -> its letter in a unit's name (`*` does not
# go into a name)
_LETTER = {"M": "M", "E": "E", "*": "A"}


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, int]] = None     # (first, count)
    norm_eps: float = 1e-5
    initializer_range: float = 0.02

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)


def unit_name(i: int, letter: str) -> str:
    """The unit of block `i` of kind `letter` (a character of the pattern)."""
    return f"blk{i}.{_LETTER[letter]}"


def _w(cfg, name):
    return ParamAttr(name=name,
                     initializer=NormalInitializer(0.0, cfg.initializer_range))


def _const(name, value):
    return ParamAttr(name=name, initializer=ConstantInitializer(value))


def _linear(cfg, x, size, name, act=None):
    return layers.fc(x, size, num_flatten_dims=2, act=act,
                     param_attr=_w(cfg, name), bias_attr=False)


def mamba_defaults(cfg: NemotronHConfig):
    """Mamba-2's own initial values of the per-head parameters: A = 1..H
    (A_log = log A), D = 1, and dt_bias the inverse softplus of time steps
    spread log-uniformly over [time_step_min, time_step_max] (here evenly in
    the log, head by head, so that they need no generator)."""
    h = cfg.mamba_num_heads
    a_log = np.log(np.arange(1, h + 1, dtype=np.float64))
    dt = np.exp(np.linspace(math.log(cfg.time_step_min),
                            math.log(cfg.time_step_max), h))
    dt = np.maximum(dt, cfg.time_step_floor)
    dt_bias = dt + np.log(-np.expm1(-dt))
    return (a_log.astype(np.float32), np.ones(h, np.float32),
            dt_bias.astype(np.float32))


def mamba_mixer(cfg: NemotronHConfig, x, pre: str):
    gn = cfg.n_groups * cfg.ssm_state_size
    with unit("mamba"):
        with unit("in_proj"):
            zxbcdt = _linear(cfg, x, cfg.d_inner + cfg.conv_dim
                             + cfg.mamba_num_heads, f"{pre}.in_proj.w")
            keep(zxbcdt)
            z, xbc, dt = layers.split(
                zxbcdt, [cfg.d_inner, cfg.conv_dim, cfg.mamba_num_heads],
                dim=2)
        with unit("conv"):
            xbc = layers.causal_conv1d(
                xbc, cfg.conv_kernel, act="silu",
                param_attr=_w(cfg, f"{pre}.conv.w"),
                bias_attr=_const(f"{pre}.conv.b", 0.0))
            xs, b, c = layers.split(xbc, [cfg.d_inner, gn, gn], dim=2)
        with unit("ssd"):
            a_log, d, dt_bias = mamba_defaults(cfg)
            y = layers.ssd_scan(
                xs, dt, b, c, cfg.mamba_num_heads, cfg.n_groups,
                chunk=cfg.chunk_size,
                a_log_attr=ParamAttr(name=f"{pre}.A_log",
                                     initializer=NumpyArrayInitializer(a_log)),
                d_attr=ParamAttr(name=f"{pre}.D",
                                 initializer=NumpyArrayInitializer(d)),
                dt_bias_attr=ParamAttr(
                    name=f"{pre}.dt_bias",
                    initializer=NumpyArrayInitializer(dt_bias)))
        with unit("norm"):
            y = layers.rms_norm(y, cfg.norm_eps, gate=z,
                                group_size=cfg.d_inner // cfg.n_groups,
                                param_attr=_const(f"{pre}.gnorm.w", 1.0))
        with unit("out_proj"):
            return _linear(cfg, y, cfg.hidden_size, f"{pre}.out_proj.w")


def attention_mixer(cfg: NemotronHConfig, x, pre: str):
    q_dim = cfg.num_heads * cfg.head_dim
    kv_dim = cfg.num_kv_heads * cfg.head_dim
    with unit("attn"):
        qkv = _linear(cfg, x, q_dim + 2 * kv_dim, f"{pre}.qkv.w")
        keep(qkv, *_ATTN_KEPT)
        q, k, v = layers.split(qkv, [q_dim, kv_dim, kv_dim], dim=2)
        ctx = layers.flash_attention(q, k, v, causal=True,
                                     num_heads=cfg.num_heads,
                                     num_kv_heads=cfg.num_kv_heads)
        return _linear(cfg, ctx, cfg.hidden_size, f"{pre}.o.w")


def moe_mixer(cfg: NemotronHConfig, x, pre: str):
    """Returns (out, pairs on each held expert, pairs held)."""
    with unit("moe"):
        routed, _, tokens, pairs = layers.moe_ffn(
            x, cfg.n_routed_experts, cfg.moe_intermediate_size,
            k=cfg.num_experts_per_tok, act="relu2",
            param_attr=_w(cfg, f"{pre}.moe"), bias_attr=False,
            experts_held=cfg.held(), scoring="sigmoid", correction_bias=True,
            norm_topk=cfg.norm_topk_prob,
            routed_scaling=cfg.routed_scaling_factor, return_counts=True)
        keep(*_MOE_KEPT)
        with unit("shared"):
            up = _linear(cfg, x, cfg.shared_intermediate_size,
                         f"{pre}.shared.up.w")
            keep(up)
            shared = _linear(cfg, layers.relu2(up), cfg.hidden_size,
                             f"{pre}.shared.down.w")
        with unit("combine"):
            return layers.elementwise_add(routed, shared), tokens, pairs


def decoder(cfg: NemotronHConfig, ids):
    """ids [B, T] -> (hidden [B, T, D] after the final norm, the expert
    blocks' counters: [(block index, TokensPerExpert, PairsHeld)])."""
    with unit("embed"):
        x = layers.embedding(ids, [cfg.vocab_size, cfg.hidden_size],
                             param_attr=_w(cfg, "embed.w"))
    counters = []
    for i, letter in enumerate(cfg.pattern):
        pre = f"blk{i}"
        if letter not in _LETTER:
            raise ValueError(f"hybrid_override_pattern: unknown block kind "
                             f"{letter!r} at {i}")
        with unit(unit_name(i, letter), remat=True):
            with unit("norm"):
                h = layers.rms_norm(x, cfg.norm_eps,
                                    param_attr=_const(f"{pre}.norm.w", 1.0))
            if letter == "M":
                out = mamba_mixer(cfg, h, pre)
            elif letter == "*":
                out = attention_mixer(cfg, h, pre)
            else:
                out, tokens, pairs = moe_mixer(cfg, h, pre)
                counters.append((i, tokens, pairs))
            x = layers.elementwise_add(x, out)
    with unit("final_norm"):
        x = layers.rms_norm(x, cfg.norm_eps,
                            param_attr=_const("final_norm.w", 1.0))
    return x, counters


def build_pretrain_program(cfg: NemotronHConfig, batch_size: int,
                           seq_len: int, optimizer_factory=None):
    """(main, startup, feed names, loss, counters) of one next-token
    pretraining step: feeds `ids` and `labels` [B, T] (the caller shifts),
    the loss the mean over all positions of the cross entropy of the untied
    head, chunked (`linear_softmax_with_cross_entropy`: the [B*T, vocab]
    logits are never held). `counters` lists, per expert block, (block
    index, TokensPerExpert, PairsHeld): fetch them where the loss is
    fetched and hand them to `record_moe_counters`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", [seq_len], dtype="int64")
        labels = layers.data("labels", [seq_len, 1], dtype="int64")
        hidden, counters = decoder(cfg, ids)
        with unit("lm_head"):
            per_token = layers.linear_softmax_with_cross_entropy(
                hidden, labels, cfg.vocab_size,
                param_attr=_w(cfg, "lm_head.w"), bias_attr=False)
        with unit("loss"):
            loss = layers.reduce_mean(per_token)
        if optimizer_factory is not None:
            optimizer_factory().minimize(loss)
    # Each block is recomputed in the backward pass from its input and from
    # what the mixers `keep`. The rule: keep a value whose remaking costs far
    # more operations a byte held than the chip's ridge (a v5e: 197 TFLOP/s
    # over 819 GB/s, 240) and recompute what costs a handful. A product 2,688
    # deep is 2,688 operations a byte of its bf16 result (the in-projection,
    # q/k/v, the shared expert's up-projection, the router's logits), the
    # attention kernel's forward about 8,000 for `out` and `lse`, the plan a
    # sort for a few integers. The convolution (8 a byte), the norms, relu^2,
    # the splits and the scan's forward (its `y` and states are 0.37 GiB a
    # layer for under 2 ms) are made again.
    main.remat_policy = "full"
    return main, startup, ["ids", "labels"], loss, counters


def record_moe_counters(counters, fetched, tokens_per_step: int, k: int):
    """Set the `moe/*` gauges of the observability registry from the fetched
    values of `counters` (in order: TokensPerExpert then PairsHeld of each
    expert block). `moe/dropped` is the pairs on held experts that no expert
    multiplied: the dispatch has no capacity, so it is what the counts say,
    0."""
    from ..observability import get_registry
    reg = get_registry()
    values = iter(fetched)
    for i, _, _ in counters:
        tokens = np.asarray(next(values))
        pairs = int(np.asarray(next(values)))
        for e, n in enumerate(tokens):
            reg.gauge("moe/tokens_per_expert", block=f"blk{i}",
                      expert=str(e)).set(int(n))
        reg.gauge("moe/pairs_held", block=f"blk{i}").set(pairs)
        reg.gauge("moe/pairs_routed", block=f"blk{i}").set(
            tokens_per_step * k)
        reg.gauge("moe/dropped", block=f"blk{i}").set(
            pairs - int(tokens.sum()))


def param_count(cfg: NemotronHConfig) -> int:
    d = cfg.hidden_size
    mamba = (d + d * (cfg.d_inner + cfg.conv_dim + cfg.mamba_num_heads)
             + cfg.conv_dim * (cfg.conv_kernel + 1) + 3 * cfg.mamba_num_heads
             + cfg.d_inner + cfg.d_inner * d)
    attn = (d + d * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
            + cfg.num_heads * cfg.head_dim * d)
    moe = (d + d * cfg.n_routed_experts + cfg.n_routed_experts
           + cfg.held()[1] * 2 * d * cfg.moe_intermediate_size
           + 2 * d * cfg.shared_intermediate_size)
    per = {"M": mamba, "*": attn, "E": moe}
    return (sum(per[c] for c in cfg.pattern) + 2 * cfg.vocab_size * d + d)
