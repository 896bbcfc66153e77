"""kimi_linear_48b_a3b: the system under test, and the counts its metrics need.

`build` goes through the program's public entry points only
(`models.kimi_linear.build_pretrain_program`, `Executor`); the one exception,
`hbm`, is the ERNIE adapter's (benchmark/program_access.py). What an adapter
of a model with expert counters does after it is built (`start` with the
routers' frozen biases, `step` with the counters fetched beside the loss,
`update_norms`) is the Nemotron adapter's `System`, taken by its public name
(JoyAI-Flash's subclasses the same); `record` is this file's, because the step
also fetches the KDA layers' decay floors. The plain reference is beside this
file, in kimi_linear_48b_a3b_reference.py, and imports none of this."""
from __future__ import annotations

from benchmark.configs import nemotron3_nano
# at import, so that a tree without the model fails when the cell is loaded
# and not after the reference has run
from paddle_tpu.models import kimi_linear

BYTES_BF16, BYTES_F32 = 2, 4


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes: required work only
# ---------------------------------------------------------------------------

def layer_kinds(cfg: dict):
    """[(is KDA, is dense)] of the layers run, the published lists counting
    from 1."""
    lin = cfg["linear_attn_config"]
    return [(i + 1 in lin["kda_layers"], i < cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def counts(cfg: dict, traffic: dict) -> dict:
    """Operations the forward pass requires a token (x3 with the backward
    pass; what remat, the experts' tiles and a recomputed chunk do not
    count), part by part, and the operations and bytes of the parts that
    have a roofline share of their own.

    The delta rule (`kda_*`) is counted from the shapes and the chunk of 64,
    whatever implements it. A token and head, forward: its row of the two
    decayed [C, C] products over K channels, of W = T K+ and of U = T V and
    of A_qk V_new, 2 C (3 K + 2 V); the three products with the [K, V]
    state, 6 K V; its share of the chunk's forward substitution, C^2 / 3.
    Bytes, what crosses the rule's boundary as the model calls it: q, k, v,
    the decay gate's raw values and o once in bf16 and beta in float32
    forward; q, k, v, the raw values, beta and o's cotangent in and the five
    cotangents out backward (ISSUE 47 reckoned a float32 log-decay in and
    out, 9.15 GB a step; the gate's softplus is made inside the op, so the
    raw values are what it must read: 7.54 GB). The forward pass made again
    in the backward pass is not required work. The routed experts' work is
    that of the pairs expected on the experts held, tokens x top_k x held /
    experts (`experts_flops_per_pair` lets a reader that knows the pairs a
    step really held count those instead)."""
    d = cfg["hidden_size"]
    t, tokens = traffic["seq_len"], traffic["batch"] * traffic["seq_len"]
    lin = cfg["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    wide, rank = h * hd, cfg["kda_gate_rank"]
    chunk, taps = cfg["kda_chunk"], lin["short_conv_kernel_size"]
    kinds = layer_kinds(cfg)
    n_kda = sum(kda for kda, _ in kinds)
    n_mla = len(kinds) - n_kda
    n_dense = sum(dense for _, dense in kinds)
    n_moe = len(kinds) - n_dense

    kda_proj_fwd = 2 * (3 * d * wide + 2 * (d * rank + rank * wide) + d * h
                        + wide * d) + 2 * 3 * wide * taps
    rule_fwd = h * (2 * chunk * (3 * hd + 2 * hd) + 6 * hd * hd
                    + chunk * chunk / 3)
    rule_bytes_fwd = 5 * wide * BYTES_BF16 + h * BYTES_F32
    rule_bytes_bwd = rule_bytes_fwd + 4 * wide * BYTES_BF16 + h * BYTES_F32

    nh = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    mla_proj_fwd = 2 * (d * nh * qk
                        + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                        + cfg["kv_lora_rank"] * nh
                        * (cfg["qk_nope_head_dim"] + dv)
                        + nh * dv * d)
    attn_kernel_fwd = 2 * t * nh * (qk + dv) // 2

    dense_fwd = 2 * 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts_published"]
    held, k = cfg["experts_held"][1], cfg["num_experts_per_token"]
    pair_fwd = 2 * 3 * d * cfg["moe_intermediate_size"]
    shared_fwd = pair_fwd * cfg["num_shared_experts"]
    pairs = tokens * k * held / experts
    moe_fwd = 2 * d * experts + shared_fwd + pair_fwd * k * held / experts
    head_fwd = 2 * d * cfg["vocab_size"]
    fwd = (n_kda * (kda_proj_fwd + rule_fwd)
           + n_mla * (mla_proj_fwd + attn_kernel_fwd)
           + n_dense * dense_fwd + n_moe * moe_fwd + head_fwd)

    # the grouped products' bytes, a layer: the held experts' three matrices
    # read in bf16 forward and backward, their gradients written in float32,
    # and a pair's row in and out, forward and backward
    expert_params = held * 3 * d * cfg["moe_intermediate_size"]
    experts_bytes = (expert_params * (2 * BYTES_BF16 + BYTES_F32)
                     + pairs * d * 4 * BYTES_BF16)
    return {
        "tokens_per_step": tokens,
        "flops_per_token": 3 * fwd,
        "fwd_flops_per_token": {
            "kda_projections": kda_proj_fwd, "kda_rule": rule_fwd,
            "mla_projections": mla_proj_fwd,
            "attention_kernel": attn_kernel_fwd,
            "dense_mlp": dense_fwd, "moe": moe_fwd, "lm_head": head_fwd},
        "kda_layers": n_kda,
        "kda_flops_per_step": 3 * rule_fwd * tokens * n_kda,
        "kda_bytes_per_step": ((rule_bytes_fwd + rule_bytes_bwd) * tokens
                               * n_kda),
        "attention_layers": n_mla,
        "experts_flops_per_pair": 3 * pair_fwd,
        "experts_pairs_per_step": pairs * n_moe,
        "experts_flops_per_step": 3 * pair_fwd * pairs * n_moe,
        "experts_bytes_per_step": experts_bytes * n_moe,
        "moe_blocks": n_moe,
        "pairs_routed_per_step": tokens * k * n_moe,
    }


def work_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def model_config(cfg: dict) -> "kimi_linear.KimiLinearConfig":
    if (cfg["moe_router_activation_func"] != "sigmoid"
            or cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1):
        raise ValueError("kimi_linear_48b_a3b: models/kimi_linear.py routes "
                         "by sigmoid scores with a selection bias and has no "
                         "group-limited routing (num_expert_group, "
                         "topk_group 1)")
    if (cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]
            or cfg["rope_scaling"] is not None):
        raise ValueError("kimi_linear_48b_a3b: latent attention is built "
                         "with a direct query product and without position "
                         "(q_lora_rank null, mla_use_nope, no rope scaling)")
    if (cfg["num_nextn_predict_layers"] or cfg["moe_layer_freq"] != 1
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]):
        raise ValueError("kimi_linear_48b_a3b: no prediction module, experts "
                         "in every layer after the dense ones, silu, an "
                         "untied head are what is built")
    lin = cfg["linear_attn_config"]
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "intermediate_size",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_experts_per_token",
            "moe_intermediate_size", "num_shared_experts",
            "routed_scaling_factor", "moe_renormalize", "rms_norm_eps",
            "initializer_range", "kda_gate_rank", "kda_chunk")
    return kimi_linear.KimiLinearConfig(
        kda_layers=list(lin["kda_layers"]),
        full_attn_layers=list(lin["full_attn_layers"]),
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        num_experts=cfg["num_experts_published"],
        experts_held=tuple(cfg["experts_held"]),
        **{key: cfg[key] for key in same})


class System(nemotron3_nano.System):
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window."""

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import paddle_tpu as fluid
        from paddle_tpu.contrib import mixed_precision as mp

        if chips != 1 or traffic.get("layout", "single") != "single":
            raise ValueError("kimi_linear_48b_a3b runs on one chip, layout "
                             "single")
        opt_cfg = cfg["optimizer"]
        self._beta1 = opt_cfg["beta1"]

        def opt():
            # called while the program is built: the schedule's op and its
            # step counter are the program's
            lr, warm = opt_cfg["learning_rate"], opt_cfg.get("warmup_steps")
            if warm:        # step t = 1, 2, ... runs at lr * min(1, t / warm)
                lr = fluid.layers.linear_lr_warmup(
                    lr, warm, start_lr=lr / warm, end_lr=lr + lr / warm)
            adam = fluid.optimizer.Adam(
                lr, beta1=opt_cfg["beta1"], beta2=opt_cfg["beta2"],
                epsilon=opt_cfg["epsilon"])
            if cfg["amp_dtype"] is None:      # float32, the CPU tests' preset
                return adam
            return mp.decorate(adam, dtype=cfg["amp_dtype"],
                               use_dynamic_loss_scaling=False)

        self._fluid, self._model = fluid, kimi_linear
        self._tokens = traffic["batch"] * traffic["seq_len"]
        self._k = cfg["num_experts_per_token"]
        with fluid.unique_name.guard():     # the same names every build
            (self.main, self.startup, _, self.loss, self.counters,
             self.floors) = kimi_linear.build_pretrain_program(
                 model_config(cfg), traffic["batch"], traffic["seq_len"],
                 optimizer_factory=opt)
        self._fetch = ([self.loss]
                       + [v for _, tokens, pairs in self.counters
                          for v in (tokens, pairs)]
                       + [floor for _, floor in self.floors])
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.program = self.main

    def record(self, counts) -> None:
        kimi_linear.record_counters(self.counters, self.floors, counts,
                                    self._tokens, self._k)


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
