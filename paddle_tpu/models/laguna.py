"""Laguna (poolside, `model_type` `laguna`): sliding-window and full
attention mixed, a head count by layer over shared key/value heads, a
per-head output gate, YaRN on a part of each full-attention head, gated
experts beside a shared expert after a leading dense layer — static-graph
builder.

Source: the public `config.json` of poolside/Laguna-XS.2. Every layer is
pre-norm residual twice over, all norms RMSNorm with one learnt weight, no
bias anywhere:

    h <- h + attn_l(RMSNorm(h));  h <- h + ffn_l(RMSNorm(h))

  attention of layer l   `n_l = num_attention_heads_per_layer[l]` query heads
                    over `num_key_value_heads` key/value heads of `head_dim`
                    (query head h reads key/value head h // (n_l / n_kv));
                    q, k, v = x W_qkv; g = sigmoid(x W_g), one scalar a query
                    head, float32. The rotary embedding turns q and k by the
                    layer kind's rule (`rope_parameters[layer_types[l]]`):
                    the first `partial_rotary_factor * head_dim` channels of
                    each head (rotate-half pairs within them), the rest pass;
                    `rope_type` `yarn` blends the inverse frequencies and
                    multiplies cosines and sines by `attention_factor`.
                    Causal softmax(q k^T head_dim^-1/2) v, and where
                    `layer_types[l]` is `sliding_attention` a query sees its
                    own key and the `sliding_window - 1` before it only;
                    out = [g_h ctx_h]_h W_o
  ffn, dense        (silu(x W1) * x W3) W2 at `intermediate_size`, where
                    `mlp_layer_types[l]` is `dense`
  ffn, experts      sigmoid scores in float32 over all `num_experts`, the
                    `num_experts_per_tok` largest chosen (no selection
                    bias), weights the chosen scores over their sum times
                    `moe_routed_scaling_factor`; gated experts of
                    `moe_intermediate_size`; plus a shared expert, the same
                    gated MLP at `shared_expert_intermediate_size`, unweighted

then a final RMSNorm and an untied head.

q, k and v are one fused [D, (n_l + 2 n_kv) head_dim] matrix (`qkv`), the
dense MLP's and the shared expert's W1 and W3 one fused [D, 2F] each
(`gate_up`): the same parameters as separate ones.

A chip of an expert-parallel deployment holds a range of each layer's experts
(`experts_held`) and a slice of the vocabulary (`vocab_size` is then the
slice's): the router keeps its full width, pairs on absent experts add
nothing here, the lookup and the loss are over the slice.

Every layer is one `core.program.unit("blk<i>", remat=True)` whose parts are
sub-units: `op_norm`, `attn/{qkv,gate,rope,kernel|swa,o}` (`kernel` in a full
layer, `swa` in a window layer; `gate` holds the gate's product, its sigmoid
and the multiply on the kernel's result), `ffn_norm`, `mlp/{gate_up,act,down}`
or `moe/{router,dispatch,experts,combine,shared}`; then `final_norm`,
`lm_head`, `loss`.

What four builders share (`_w`, `_linear`, `_norm`, the gated MLP, the routed
experts' wrapper) is taken from the two that wrote it and not written again.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import keep, unit
from paddle_tpu.models.joyai_flash import (_gated_mlp, _linear, _norm, _w,
                                           dense_mlp)
from paddle_tpu.models.lfm2 import experts as routed_experts
from paddle_tpu.models.nemotron_h import record_moe_counters  # noqa: F401
from paddle_tpu.ops.pallas_kernels.flash_attention import KEPT as _ATTN_KEPT

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def _published_layer_types() -> List[str]:
    return [FULL if i % 4 == 0 else SLIDING for i in range(40)]


def _published_rope() -> Dict[str, dict]:
    return {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
    }


@dataclass
class LagunaConfig:
    """The published keys of Laguna-XS.2 (defaults), and `experts_held`."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    gating: bool = True
    sliding_window: int = 512
    layer_types: List[str] = field(default_factory=_published_layer_types)
    num_attention_heads_per_layer: List[int] = field(
        default_factory=lambda: [48 if i % 4 == 0 else 64 for i in range(40)])
    rope_parameters: Dict[str, dict] = field(default_factory=_published_rope)
    mlp_layer_types: List[str] = field(
        default_factory=lambda: [DENSE] + [SPARSE] * 39)
    # experts
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    moe_apply_router_weight_on_input: bool = False
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, int]] = None     # (first, count)
    initializer_range: float = 0.02
    # what the shared experts' wrapper (`models/lfm2.py`) reads besides
    use_expert_bias: bool = False

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor

    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def heads(self, layer: int) -> int:
        return self.num_attention_heads_per_layer[layer]

    def check(self) -> None:
        n = self.num_hidden_layers
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            if len(getattr(self, key)) < n:
                raise ValueError(f"laguna: {key} names "
                                 f"{len(getattr(self, key))} layers of {n}")
        for i in range(n):
            if self.layer_types[i] not in (FULL, SLIDING):
                raise ValueError(f"layer_types: unknown attention "
                                 f"{self.layer_types[i]!r} at {i}")
            if self.mlp_layer_types[i] not in (DENSE, SPARSE):
                raise ValueError(f"mlp_layer_types: unknown feed-forward "
                                 f"{self.mlp_layer_types[i]!r} at {i}")
            if self.heads(i) % self.num_key_value_heads:
                raise ValueError(
                    f"num_attention_heads_per_layer: {self.heads(i)} query "
                    f"heads at {i} are not a multiple of the "
                    f"{self.num_key_value_heads} key/value heads")
        if not self.gating or self.moe_apply_router_weight_on_input:
            raise ValueError("laguna: the per-head output gate is built in, "
                             "and the router's weight goes on the experts' "
                             "output")


def rope_arguments(cfg: LagunaConfig, kind: str) -> dict:
    """`layers.rotary_embedding`'s keywords for a layer kind's published
    rule: nothing but `theta` where the whole head turns by the default
    frequencies."""
    rule = cfg.rope_parameters[kind]
    args = {"theta": float(rule["rope_theta"])}
    rotary_dim = int(cfg.head_dim * rule.get("partial_rotary_factor", 1))
    if rotary_dim != cfg.head_dim:
        args["rotary_dim"] = rotary_dim
    if rule.get("rope_type", "default") == "yarn":
        args["yarn"] = {key: rule[key] for key in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow")}
        args["attention_factor"] = rule.get("attention_factor")
    elif rule.get("rope_type", "default") != "default":
        raise ValueError(f"laguna: rope_type {rule['rope_type']!r} is not "
                         f"built (default, yarn)")
    return args


def attention(cfg: LagunaConfig, x, pre: str, layer: int):
    """x [B, T, D] -> [B, T, D]: layer `layer`'s attention, its kind and its
    head count the configuration's."""
    t, hd = x.shape[1], cfg.head_dim
    nh, nkv = cfg.heads(layer), cfg.num_key_value_heads
    kind = cfg.layer_types[layer]
    q_dim, kv_dim = nh * hd, nkv * hd
    with unit("attn"):
        with unit("qkv"):
            qkv = _linear(cfg, x, q_dim + 2 * kv_dim, f"{pre}.qkv.w")
            keep(qkv)
            q, k, v = layers.split(qkv, [q_dim, kv_dim, kv_dim], dim=2)
        with unit("gate"):
            gate = layers.sigmoid(layers.cast(
                _linear(cfg, x, nh, f"{pre}.gate.w"), "float32"))
        with unit("rope"):
            qk = layers.rotary_embedding(
                layers.concat([q, k], axis=2), nh + nkv,
                **rope_arguments(cfg, kind))
            q, k = layers.split(qk, [q_dim, kv_dim], dim=2)
        with unit("swa" if kind == SLIDING else "kernel"):
            keep(*_ATTN_KEPT)
            ctx = layers.flash_attention(
                q, k, v, causal=True, num_heads=nh, num_kv_heads=nkv,
                window=cfg.sliding_window if kind == SLIDING else None)
        with unit("gate"):
            # one scalar a head on its 128 channels, the product in float32
            gated = layers.elementwise_mul(
                layers.cast(layers.reshape(ctx, [0, t, nh, hd]), "float32"),
                layers.reshape(gate, [0, t, nh, 1]))
            gated = layers.reshape(gated, [0, t, q_dim])
        with unit("o"):
            return _linear(cfg, gated, cfg.hidden_size, f"{pre}.o.w")


def experts(cfg: LagunaConfig, x, pre: str):
    """Returns (out, pairs on each held expert, pairs held): the held routed
    experts' part plus the shared expert's."""
    routed, tokens, pairs = routed_experts(cfg, x, pre)
    with unit("moe"):
        with unit("shared"):
            shared = _gated_mlp(cfg, x, cfg.shared_expert_intermediate_size,
                                f"{pre}.shared")
        with unit("combine"):
            return layers.elementwise_add(routed, shared), tokens, pairs


def decoder(cfg: LagunaConfig, ids):
    """ids [B, T] -> (hidden [B, T, D] after the final norm, the expert
    layers' counters: [(layer index, TokensPerExpert, PairsHeld)])."""
    cfg.check()
    with unit("embed"):
        x = layers.embedding(ids, [cfg.vocab_size, cfg.hidden_size],
                             param_attr=_w(cfg, "embed.w"))
    counters = []
    for i in range(cfg.num_hidden_layers):
        pre = f"blk{i}"
        with unit(pre, remat=True):
            with unit("op_norm"):
                h = _norm(cfg, x, f"{pre}.op_norm.w")
            x = layers.elementwise_add(x, attention(cfg, h, pre, i))
            with unit("ffn_norm"):
                h = _norm(cfg, x, f"{pre}.ffn_norm.w")
            if cfg.mlp_layer_types[i] == DENSE:
                out = dense_mlp(cfg, h, pre)
            else:
                out, tokens, pairs = experts(cfg, h, pre)
                counters.append((i, tokens, pairs))
            x = layers.elementwise_add(x, out)
    with unit("final_norm"):
        x = _norm(cfg, x, "final_norm.w")
    return x, counters


def build_pretrain_program(cfg: LagunaConfig, batch_size: int, seq_len: int,
                           optimizer_factory=None):
    """(main, startup, feed names, loss, counters) of one next-token
    pretraining step: feeds `ids` and `labels` [B, T] (the caller shifts),
    the loss the mean over all positions of the cross entropy of the untied
    head, chunked (`linear_softmax_with_cross_entropy`). `counters` lists,
    per expert layer, (layer index, TokensPerExpert, PairsHeld): fetch them
    where the loss is fetched and hand them to `record_moe_counters`."""
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
    # Each layer is recomputed in the backward pass from its input (the
    # float32 residual stream) and from what it keeps. PR 29's rule: keep
    # what costs far more operations a byte held than the chip's ridge (240
    # on a v5e) and is small: the attention kernel's forward (`out` and
    # `lse`: about 8,000 operations a byte at T 8,192 in a full layer, 500
    # in a window layer), the dense MLP's and the shared expert's gate and
    # up (products 2,048 deep), the router's logits and the plan (a sort for
    # a few integers). q, k and v are a product 2,048 deep too, but 16 to
    # 20 KB a token a layer (a window layer's q alone is 8,192 wide): they
    # are made again, as are the gate, the norms, the rotation, the
    # activations and the experts' hidden halves.
    main.remat_policy = "full"
    return main, startup, ["ids", "labels"], loss, counters


def param_count(cfg: LagunaConfig, touched: bool = False) -> int:
    """Trained parameters; with `touched` the active ones, as a model card
    counts them: everything but the experts a token is not routed to
    (`num_experts_per_tok` of an expert layer's stay, the table whole)."""
    d, hd = cfg.hidden_size, cfg.head_dim
    kv = cfg.num_key_value_heads * hd
    expert = 3 * d * cfg.moe_intermediate_size
    n_experts = cfg.num_experts_per_tok if touched else cfg.held()[1]
    moe = (d * cfg.num_experts + n_experts * expert
           + 3 * d * cfg.shared_expert_intermediate_size)
    total = 2 * cfg.vocab_size * d + d
    for i in range(cfg.num_hidden_layers):
        q = cfg.heads(i) * hd
        total += 2 * d + d * (q + 2 * kv) + d * cfg.heads(i) + q * d
        total += (3 * d * cfg.intermediate_size
                  if cfg.mlp_layer_types[i] == DENSE else moe)
    return total
