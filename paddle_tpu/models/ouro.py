"""Ouro: a looped decoder — one stack of layers run several times over the
same weights, with a learned exit gate — static-graph builder.

Source: the public `config.json` of ByteDance/Ouro-2.6B (`model_type`
`ouro`; "Scaling Latent Reasoning via Looped Language Models", 2025): a
stack of `num_layers` decoder layers is applied `total_ut_steps` times, pass
after pass, to the same hidden state with the same parameters. A layer is
sandwich-normed,

    a = Attn(RMSNorm1(h));  h <- h + RMSNorm2(a)
    m = MLP(RMSNorm3(h));   h <- h + RMSNorm4(m)

with full causal attention under rotary position embedding (rotate-half, on
the whole head) and a silu-gated MLP, no bias anywhere. After the last layer
of a pass the state goes through one final RMSNorm (one weight for all
passes); that normed state is the pass's exit and the next pass's input.
Every exit is projected by the one untied head; a gate `sigmoid(h . w + b)`
a position and pass gives the exit distribution `p` (`layers.loop_exit_gate`)
and the loss is the mean over the positions of `sum_t p_t CE_t - beta H(p)`
(`layers.loop_exit_loss`).

The passes are unrolled when the program is built: application `t` of layer
`i` is the remat block `unit("blk<i>.u<t>", remat=True)` and creates its
parameters under the names `blk<i>.*` — the same names in every pass, which
is what makes them the same parameters (`LayerHelper.create_parameter`
returns the parameter a name already has). Sub-units: `norm1`,
`attn/{qkv,rope,kernel,o}`, `norm2`, `norm3`, `mlp/{gate_up,act,down}`,
`norm4`; then `final_norm.u<t>`, `exit_gate`, `lm_head`, `loss`. The blocks
are rematerialised (`Program.remat_policy = "full"`) and keep what PR 29's
rule says is dear to remake and cheap to hold (at `build_pretrain_program`).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import keep, unit
from paddle_tpu.initializer import ConstantInitializer, NormalInitializer
from paddle_tpu.ops.pallas_kernels.flash_attention import KEPT as _ATTN_KEPT
from paddle_tpu.param_attr import ParamAttr


@dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    total_ut_steps: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    entropy_beta: float = 0.05
    initializer_range: float = 0.02


def _w(cfg, name):
    return ParamAttr(name=name,
                     initializer=NormalInitializer(0.0, cfg.initializer_range))


def _const(name, value):
    return ParamAttr(name=name, initializer=ConstantInitializer(value))


def _linear(cfg, x, size, name):
    return layers.fc(x, size, num_flatten_dims=2, param_attr=_w(cfg, name),
                     bias_attr=False)


def _norm(cfg, x, name):
    return layers.rms_norm(x, cfg.rms_norm_eps, param_attr=_const(name, 1.0))


def layer(cfg: OuroConfig, h, pre: str, keep_qkv: bool = False):
    """One application of the layer whose parameters are `pre`.*; with
    `keep_qkv` its remat block keeps the q/k/v product too."""
    qd = cfg.num_heads * cfg.head_dim
    with unit("norm1"):
        x = _norm(cfg, h, f"{pre}.norm1.w")
    with unit("attn"):
        with unit("qkv"):
            qkv = _linear(cfg, x, 3 * qd, f"{pre}.qkv.w")
            if keep_qkv:
                keep(qkv)
        with unit("rope"):
            qk, v = layers.split(qkv, [2 * qd, qd], dim=2)
            qk = layers.rotary_embedding(qk, 2 * cfg.num_heads,
                                         theta=cfg.rope_theta)
            q, k = layers.split(qk, 2, dim=2)
        with unit("kernel"):
            keep(*_ATTN_KEPT)
            ctx = layers.flash_attention(q, k, v, causal=True,
                                         num_heads=cfg.num_heads)
        with unit("o"):
            a = _linear(cfg, ctx, cfg.hidden_size, f"{pre}.o.w")
    with unit("norm2"):
        h = layers.elementwise_add(h, _norm(cfg, a, f"{pre}.norm2.w"))
    with unit("norm3"):
        x = _norm(cfg, h, f"{pre}.norm3.w")
    with unit("mlp"):
        with unit("gate_up"):
            gu = _linear(cfg, x, 2 * cfg.intermediate_size,
                         f"{pre}.gate_up.w")
        with unit("act"):
            act = layers.swiglu(*layers.split(gu, 2, dim=2))
        with unit("down"):
            m = _linear(cfg, act, cfg.hidden_size, f"{pre}.down.w")
    with unit("norm4"):
        return layers.elementwise_add(h, _norm(cfg, m, f"{pre}.norm4.w"))


def shared(t: int, i: int) -> str:
    """The parameters of layer `i` in pass `t`: the same in every pass."""
    return f"blk{i}"


def decoder(cfg: OuroConfig, ids, layer_prefix=shared):
    """ids [B, T] -> the exits' states, one [B, T, D] a pass (each after the
    final norm). `layer_prefix(t, i)` names the parameters application
    (pass t, layer i) reads; a test hands every application a prefix of its
    own to build the untied model the loop is compared with."""
    with unit("embed"):
        h = layers.embedding(ids, [cfg.vocab_size, cfg.hidden_size],
                             param_attr=_w(cfg, "embed.w"))
    exits = []
    for t in range(1, cfg.total_ut_steps + 1):
        for i in range(cfg.num_layers):
            with unit(f"blk{i}.u{t}", remat=True):
                h = layer(cfg, h, layer_prefix(t, i),
                          keep_qkv=t > cfg.total_ut_steps // 2)
        with unit(f"final_norm.u{t}"):
            h = _norm(cfg, h, "final_norm.w")
        exits.append(h)
    return exits


def objective(cfg: OuroConfig, exits, labels, seq_len: int):
    """(loss, ExitShare [passes], ExitEntropy []) from the exits' states and
    the labels [B, T, 1]. The states and the labels, once a pass, go through
    ONE `linear_softmax_with_cross_entropy` of passes x B x T rows (one
    gradient accumulator of the head's matrix; of the logits a chunk of rows
    is held at a time, and made again in the backward pass); its per-row
    loss is weighted by the exit distribution."""
    passes = len(exits)
    states = layers.reshape(layers.concat(exits, axis=0),
                            [passes, -1, seq_len, cfg.hidden_size])
    with unit("exit_gate"):
        p = layers.loop_exit_gate(
            states, param_attr=_const("exit_gate.w", 0.0),
            bias_attr=_const("exit_gate.b", 0.0))
    with unit("lm_head"):
        ce = layers.linear_softmax_with_cross_entropy(
            states, layers.stack([labels] * passes, axis=0),
            cfg.vocab_size, param_attr=_w(cfg, "lm_head.w"),
            bias_attr=False)
    with unit("loss"):
        return layers.loop_exit_loss(p, ce, beta=cfg.entropy_beta)


def build_pretrain_program(cfg: OuroConfig, batch_size: int, seq_len: int,
                           optimizer_factory=None):
    """(main, startup, feed names, loss, counters) of one next-token
    pretraining step: feeds `ids` and `labels` [B, T] (the caller shifts).
    `counters` is (ExitShare [passes], ExitEntropy []): fetch them where the
    loss is fetched and hand them to `record_loop_counters`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", [seq_len], dtype="int64")
        labels = layers.data("labels", [seq_len, 1], dtype="int64")
        loss, share, entropy = objective(cfg, decoder(cfg, ids), labels,
                                         seq_len)
        if optimizer_factory is not None:
            optimizer_factory().minimize(loss)
    # Each application is recomputed in the backward pass from its input (the
    # float32 residual stream, 8 KB a token) and from what it keeps. PR 29's
    # rule: keep what costs far more operations a byte held than the chip's
    # ridge (240 on a v5e). The q/k/v product is 2,048 operations a byte of
    # its bf16 result, the attention kernel's forward about 4,000 at T 4,096
    # for `out` and `lse`; the gate/up product is as dear (2,048) but 22.5
    # KB a token and application, which 32 applications cannot hold. The
    # norms, the rotation, the activation and the splits cost a handful and
    # are made again. At the published widths, 8 layers and 8,192 tokens the
    # kernels' values are 1.0 GiB over the 32 applications and the q/k/v
    # products 3.0: beside 6.84 GiB of state a v5e holds half of the latter,
    # so the later half of the passes keeps it (`decoder`).
    main.remat_policy = "full"
    return main, startup, ["ids", "labels"], loss, (share, entropy)


def record_loop_counters(fetched_share, fetched_entropy) -> None:
    """Set the `loop/*` gauges of the observability registry from the
    fetched values of a step's counters."""
    from ..observability import get_registry
    reg = get_registry()
    share = np.asarray(fetched_share, dtype=np.float64).reshape(-1)
    reg.gauge("loop/passes").set(len(share))
    for t, s in enumerate(share, start=1):
        reg.gauge("loop/exit_share", **{"pass": str(t)}).set(float(s))
    reg.gauge("loop/exit_entropy").set(float(np.asarray(fetched_entropy)))


def param_count(cfg: OuroConfig) -> int:
    d, qd = cfg.hidden_size, cfg.num_heads * cfg.head_dim
    per_layer = 4 * d * qd + 3 * d * cfg.intermediate_size + 4 * d
    return (cfg.num_layers * per_layer + 2 * cfg.vocab_size * d
            + d + (d + 1))
