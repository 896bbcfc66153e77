"""JoyAI-LLM-Flash (the DeepSeek-V3 layout): multi-head latent attention,
gated experts beside a shared expert after a leading dense layer, one
multi-token-prediction module — static-graph builder.

Source: the public `config.json` of jdopensource/JoyAI-LLM-Flash
(`model_type` `joyai_llm_flash`), whose keys follow `deepseek_v3`; the
DeepSeek-V3 report (arXiv:2412.19437) sections 2.1-2.2 for the equations.
Every layer is pre-norm residual twice over, all norms RMSNorm with one
learnt weight, no bias anywhere:

    h <- h + attn(RMSNorm(h));  h <- h + ffn(RMSNorm(h))

  latent attention  c_q = RMSNorm(x W_qa) [q_lora_rank]; q = c_q W_qb, H
                    heads of `qk_nope_head_dim + qk_rope_head_dim`.
                    a = x W_kva; c_kv = RMSNorm(a[:kv_lora_rank]); k_rope =
                    a[kv_lora_rank:], ONE head shared by all H.
                    c_kv W_kvb -> H heads of k_nope ‖ v (`qk_nope_head_dim`
                    + `v_head_dim`). The rotary embedding turns q's rope part
                    and k_rope only, pairs interleaved (2j, 2j + 1).
                    q = q_nope ‖ q_rope, k = k_nope ‖ k_rope (broadcast to
                    all heads); causal softmax(q k^T (nope + rope)^-1/2) v;
                    out = ctx W_o. Keys are wider than values (192 / 128).
  ffn, dense        (silu(x W1) * x W3) W2, the first `first_k_dense_replace`
                    layers
  ffn, experts      sigmoid scores in float32 over all `n_routed_experts`,
                    the `num_experts_per_tok` largest of score + bias chosen
                    (`noaux_tc` with one group: no group limit; the bias a
                    buffer without gradient), weights the chosen scores over
                    their sum times `routed_scaling_factor`; gated experts;
                    plus a shared expert, the same gated MLP at width
                    `moe_intermediate_size * n_shared_experts`, unweighted

then a final RMSNorm and an untied head. The multi-token-prediction module
(depth 1) reads the trunk's state h_i before the final norm and the next
token's embedding from THE SAME table:

    h'_i = [RMSNorm_e(Emb(t_{i+1})) ‖ RMSNorm_h(h_i)] W_eh

then one block (latent attention + experts, weights of its own), a final
norm of its own and THE SAME head matrix against t_{i+2}; the last position
of a sequence has no target. The step minimises L_main + `mtp_loss_weight`
L_mtp.

No matrix is multiplied into another: the parameters are the published ones
(`q_a`, `q_a_norm`, `q_b`, `kv_a`, `kv_a_norm`, `kv_b`, `o`; an expert's
`w1`, `w3`, `w2`; `eh_proj`, `enorm`, `hnorm`); the absorbed form of latent
attention is decoding's. The dense MLP's and the shared expert's W1 and W3
are one fused [D, 2F] matrix each (`gate_up`), the same parameters as two.

A chip of an expert-parallel deployment holds a range of each layer's experts
(`experts_held`) and a slice of the vocabulary (`vocab_size` is then the
slice's): the router keeps its full width, pairs on absent experts add
nothing here, both lookups and both losses are over the slice.

Every layer is one `core.program.unit("blk<i>", remat=True)` whose parts are
sub-units: `op_norm`, `attn/{q_a,q_norm,q_b,kv_a,kv_norm,kv_b,rope,assemble,
kernel,o}`, `ffn_norm`, `mlp/{gate_up,act,down}` or `moe/{router,dispatch,
experts,combine,shared}`; then `final_norm`, `lm_head`, `loss`; the module is
`mtp/{embed,enorm,hnorm,eh_proj}`, `mtp/blk/...` as a layer, `mtp/final_norm`,
`mtp/head`, `mtp/loss`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import keep, unit
from paddle_tpu.initializer import ConstantInitializer, NormalInitializer
from paddle_tpu.models.nemotron_h import record_moe_counters
from paddle_tpu.ops.pallas_kernels.flash_attention import KEPT as _ATTN_KEPT
from paddle_tpu.parallel.moe import KEPT as _MOE_KEPT
from paddle_tpu.param_attr import ParamAttr

IGNORE = -100
MTP_BLOCK = "_mtp"      # the module's block among the expert counters


@dataclass
class JoyaiFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    intermediate_size: int = 7168
    # latent attention
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rope_interleave: bool = True
    mla_use_nope: bool = False      # True: nothing is rotated
    # experts
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, int]] = None     # (first, count)
    # multi-token prediction
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)


def _w(cfg, name):
    return ParamAttr(name=name,
                     initializer=NormalInitializer(0.0, cfg.initializer_range))


def _norm(cfg, x, name):
    return layers.rms_norm(
        x, cfg.rms_norm_eps, param_attr=ParamAttr(
            name=name, initializer=ConstantInitializer(1.0)))


def _linear(cfg, x, size, name):
    return layers.fc(x, size, num_flatten_dims=2, param_attr=_w(cfg, name),
                     bias_attr=False)


def latent_attention(cfg, x, pre: str):
    """x [B, T, D] -> [B, T, D]. The two low-rank products' results (1,536
    and 576 wide) stay for the backward pass; q, k and v (6,144, 6,144 and
    4,096 wide) are made again from them.

    Two settings of other models that share the layer (`models/
    kimi_linear.py`): `cfg.q_lora_rank` None takes q straight from x (one
    product `<pre>.q.w` under the unit `q_b`; no latent, no `q_norm`), and
    `cfg.mla_use_nope` leaves q's 64-wide part and the one shared key head
    unrotated (no `rope` unit; k is still assembled to 192 channels a
    head)."""
    t = x.shape[1]
    nh, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim, cfg.v_head_dim)
    rotate = not cfg.mla_use_nope
    with unit("attn"):
        if cfg.q_lora_rank is None:
            c_q, q_w = x, f"{pre}.q.w"
        else:
            with unit("q_a"):
                q_a = _linear(cfg, x, cfg.q_lora_rank, f"{pre}.q_a.w")
                keep(q_a)
            with unit("q_norm"):
                c_q, q_w = _norm(cfg, q_a, f"{pre}.q_a_norm.w"), f"{pre}.q_b.w"
        with unit("q_b"):
            q = layers.reshape(
                _linear(cfg, c_q, nh * cfg.qk_head_dim, q_w),
                [0, t, nh, cfg.qk_head_dim])
        with unit("kv_a"):
            kv_a = _linear(cfg, x, cfg.kv_lora_rank + rope, f"{pre}.kv_a.w")
            keep(kv_a)
            c_kv, k_rope = layers.split(kv_a, [cfg.kv_lora_rank, rope], dim=2)
        with unit("kv_norm"):
            c_kv = _norm(cfg, c_kv, f"{pre}.kv_a_norm.w")
        with unit("kv_b"):
            kv = layers.reshape(
                _linear(cfg, c_kv, nh * (nope + dv), f"{pre}.kv_b.w"),
                [0, t, nh, nope + dv])
            k_nope, v = layers.split(kv, [nope, dv], dim=3)
        if rotate:
            with unit("rope"):
                # q's rope parts and the one shared key head, nh + 1 heads
                # of `rope` channels in one rotation
                q_nope, q_rope = layers.split(q, [nope, rope], dim=3)
                turned = layers.rotary_embedding(
                    layers.concat([layers.reshape(q_rope, [0, t, nh * rope]),
                                   k_rope], axis=2),
                    nh + 1, theta=cfg.rope_theta,
                    interleaved=cfg.rope_interleave)
                q_rope, k_rope = layers.split(turned, [nh * rope, rope],
                                              dim=2)
        with unit("assemble"):
            if rotate:
                q = layers.concat(
                    [q_nope, layers.reshape(q_rope, [0, t, nh, rope])],
                    axis=3)
            k = layers.concat(
                [k_nope, layers.expand(layers.reshape(k_rope,
                                                      [0, t, 1, rope]),
                                       [1, 1, nh, 1])], axis=3)
            q = layers.reshape(q, [0, t, nh * cfg.qk_head_dim])
            k = layers.reshape(k, [0, t, nh * cfg.qk_head_dim])
            v = layers.reshape(v, [0, t, nh * dv])
        with unit("kernel"):
            keep(*_ATTN_KEPT)
            ctx = layers.flash_attention(q, k, v, causal=True, num_heads=nh)
        with unit("o"):
            return _linear(cfg, ctx, cfg.hidden_size, f"{pre}.o.w")


def _gated_mlp(cfg, x, width: int, pre: str):
    with unit("gate_up"):
        gu = _linear(cfg, x, 2 * width, f"{pre}.gate_up.w")
        keep(gu)
    with unit("act"):
        act = layers.swiglu(*layers.split(gu, 2, dim=2))
    with unit("down"):
        return _linear(cfg, act, cfg.hidden_size, f"{pre}.down.w")


def dense_mlp(cfg: JoyaiFlashConfig, x, pre: str):
    with unit("mlp"):
        return _gated_mlp(cfg, x, cfg.intermediate_size, pre)


def experts(cfg: JoyaiFlashConfig, x, pre: str):
    """Returns (out, pairs on each held expert, pairs held): the held routed
    experts' part plus the shared expert's."""
    with unit("moe"):
        routed, _, tokens, pairs = layers.moe_ffn(
            x, cfg.n_routed_experts, cfg.moe_intermediate_size,
            k=cfg.num_experts_per_tok, act="silu", gated=True,
            param_attr=_w(cfg, f"{pre}.moe"), bias_attr=False,
            experts_held=cfg.held(), scoring="sigmoid", correction_bias=True,
            norm_topk=cfg.norm_topk_prob,
            routed_scaling=cfg.routed_scaling_factor, return_counts=True)
        keep(*_MOE_KEPT)
        with unit("shared"):
            shared = _gated_mlp(
                cfg, x, cfg.moe_intermediate_size * cfg.n_shared_experts,
                f"{pre}.shared")
        with unit("combine"):
            return layers.elementwise_add(routed, shared), tokens, pairs


def _layer(cfg, x, pre: str, dense: bool):
    """One pre-norm residual layer on the float32 stream x; returns (x, the
    expert counters or None)."""
    with unit("op_norm"):
        h = _norm(cfg, x, f"{pre}.op_norm.w")
    x = layers.elementwise_add(x, latent_attention(cfg, h, pre))
    with unit("ffn_norm"):
        h = _norm(cfg, x, f"{pre}.ffn_norm.w")
    if dense:
        return layers.elementwise_add(x, dense_mlp(cfg, h, pre)), None
    out, tokens, pairs = experts(cfg, h, pre)
    return layers.elementwise_add(x, out), (tokens, pairs)


def decoder(cfg: JoyaiFlashConfig, ids):
    """ids [B, T] -> (the trunk's state [B, T, D] BEFORE the final norm, the
    expert layers' counters: [(layer index, TokensPerExpert, PairsHeld)])."""
    with unit("embed"):
        x = layers.embedding(ids, [cfg.vocab_size, cfg.hidden_size],
                             param_attr=_w(cfg, "embed.w"))
    counters = []
    for i in range(cfg.num_hidden_layers):
        pre = f"blk{i}"
        with unit(pre, remat=True):
            x, counts = _layer(cfg, x, pre, i < cfg.first_k_dense_replace)
        if counts is not None:
            counters.append((i, *counts))
    return x, counters


def mtp_module(cfg: JoyaiFlashConfig, state, labels):
    """The depth-1 prediction module: `state` [B, T, D] is the trunk's h_i,
    `labels` [B, T, 1] the tokens t_{i+1}. Returns (its state after its own
    final norm, its block's counters). The table is the trunk's `embed.w`."""
    with unit("mtp"):
        with unit("embed"):
            e = layers.embedding(
                layers.reshape(labels, [0, labels.shape[1]]),
                [cfg.vocab_size, cfg.hidden_size],
                param_attr=_w(cfg, "embed.w"))
        with unit("enorm"):
            e = _norm(cfg, e, "mtp.enorm.w")
        with unit("hnorm"):
            h = _norm(cfg, state, "mtp.hnorm.w")
        with unit("eh_proj"):
            x = _linear(cfg, layers.concat([e, h], axis=2), cfg.hidden_size,
                        "mtp.eh_proj.w")
        with unit("blk", remat=True):
            x, counts = _layer(cfg, x, "mtp.blk", dense=False)
        with unit("final_norm"):
            x = _norm(cfg, x, "mtp.final_norm.w")
    return x, (MTP_BLOCK, *counts)


def build_pretrain_program(cfg: JoyaiFlashConfig, batch_size: int,
                           seq_len: int, optimizer_factory=None):
    """(main, startup, feed names, loss, counters, terms) of one pretraining
    step: feeds `ids` and `labels` [B, T] (the caller shifts: a position's
    label is its next token). The loss is `L_main + mtp_loss_weight * L_mtp`:
    L_main the mean over all positions of the cross entropy of the untied
    head; L_mtp the mean over the positions that have one (all but a
    sequence's last) of the module's cross entropy against the token after
    the label, through the same head matrix, its targets the labels one
    position on. Both heads are chunked
    (`linear_softmax_with_cross_entropy`). The head matrix `lm_head.w` and
    the table `embed.w` are one parameter each with two readers: one Adam
    slot, a gradient that is the sum of both readers'.

    `counters` lists (layer index or `MTP_BLOCK`, TokensPerExpert, PairsHeld)
    per expert layer, the module's last; `terms` = {"main", "mtp"}, the two
    losses unweighted. Fetch them where the loss is fetched and hand them to
    `record_counters`."""
    if cfg.num_nextn_predict_layers != 1:
        raise ValueError("joyai_flash: one multi-token-prediction module is "
                         f"built, not {cfg.num_nextn_predict_layers}")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", [seq_len], dtype="int64")
        labels = layers.data("labels", [seq_len, 1], dtype="int64")
        state, counters = decoder(cfg, ids)
        with unit("final_norm"):
            hidden = _norm(cfg, state, "final_norm.w")
        with unit("lm_head"):
            per_token = layers.linear_softmax_with_cross_entropy(
                hidden, labels, cfg.vocab_size,
                param_attr=_w(cfg, "lm_head.w"), bias_attr=False)
        with unit("loss"):
            main_loss = layers.reduce_mean(per_token)
        mtp_hidden, mtp_counts = mtp_module(cfg, state, labels)
        counters.append(mtp_counts)
        with unit("mtp"):
            with unit("head"):
                # the module's target at i is the label at i + 1; the last
                # position of a sequence has none
                targets = layers.pad(
                    layers.slice(labels, [1], [1], [seq_len]),
                    [0, 0, 0, 1, 0, 0], pad_value=IGNORE)
                mtp_token = layers.linear_softmax_with_cross_entropy(
                    mtp_hidden, targets, cfg.vocab_size, ignore_index=IGNORE,
                    param_attr=_w(cfg, "lm_head.w"), bias_attr=False)
            with unit("loss"):
                mtp_loss = layers.scale(
                    layers.reduce_sum(mtp_token),
                    scale=1.0 / (batch_size * (seq_len - 1)))
                loss = layers.elementwise_add(
                    main_loss, layers.scale(mtp_loss,
                                            scale=cfg.mtp_loss_weight))
        if optimizer_factory is not None:
            optimizer_factory().minimize(loss)
    # Each block is recomputed in the backward pass from its input (the
    # float32 residual stream) and from what it keeps. PR 29's rule: keep
    # what costs far more operations a byte held than the chip's ridge (240
    # on a v5e) and is small. The two down-projections' results are products
    # 2,048 deep, 3 KB and 1.1 KB a token; the dense MLP's and the shared
    # expert's gate and up 28 KB (one layer) and 3 KB; the attention
    # kernel's forward about 12,000 a byte of `out` and `lse` at T 8,192;
    # the router's logits and the plan a sort for a few integers. q, k and v
    # are products 1,536 and 512 deep but 32 KB a token together: made
    # again, as are the norms, the rotation, the concatenations, the
    # activations and the experts' hidden halves.
    main.remat_policy = "full"
    terms = {"main": main_loss, "mtp": mtp_loss}
    return main, startup, ["ids", "labels"], loss, counters, terms


def record_counters(counters, fetched, tokens_per_step: int, k: int):
    """Set the `moe/*` gauges (`nemotron_h.record_moe_counters`, the
    module's block under `block="blk_mtp"`) from the fetched values of
    `counters`, and `mtp/loss` from the fetched `terms["mtp"]` that follows
    them (the trunk's term is the fetched loss less `mtp_loss_weight` times
    it)."""
    from ..observability import get_registry
    *moe, mtp_loss = fetched
    record_moe_counters(counters, moe, tokens_per_step, k)
    get_registry().gauge("mtp/loss").set(float(np.asarray(mtp_loss)))


def param_count(cfg: JoyaiFlashConfig) -> int:
    """Trained parameters (the routers' bias buffers are not)."""
    d, nh = cfg.hidden_size, cfg.num_attention_heads
    attn = (d * cfg.q_lora_rank + cfg.q_lora_rank
            + cfg.q_lora_rank * nh * cfg.qk_head_dim
            + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) + cfg.kv_lora_rank
            + cfg.kv_lora_rank * nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + nh * cfg.v_head_dim * d)
    mlp = 3 * d * cfg.intermediate_size
    moe = (d * cfg.n_routed_experts
           + (cfg.held()[1] + cfg.n_shared_experts)
           * 3 * d * cfg.moe_intermediate_size)
    layer = 2 * d + attn
    total = 2 * cfg.vocab_size * d + d
    for i in range(cfg.num_hidden_layers):
        total += layer + (mlp if i < cfg.first_k_dense_replace else moe)
    # the module: two norms, eh_proj, one expert block, its final norm
    total += 2 * d + 2 * d * d + layer + moe + d
    return total
