"""joyai_llm_flash: the system under test, and the counts its metrics need.

`build` goes through the program's public entry points only
(`models.joyai_flash.build_pretrain_program`, `Executor`); the one exception,
`hbm`, is the ERNIE adapter's (benchmark/program_access.py). What an adapter
of a model with expert counters does after it is built (`start` with the
routers' frozen biases, `step` with the counters fetched beside the loss,
`update_norms`) is the Nemotron adapter's `System`, taken by its public name;
`record` is this file's, because the step also fetches the module's loss term.
The plain reference is beside this file, in joyai_llm_flash_reference.py, and
imports none of this."""
from __future__ import annotations

from benchmark.configs import nemotron3_nano
# at import, so that a tree without the model fails when the cell is loaded
# and not after the reference has run
from paddle_tpu.models import joyai_flash

BYTES_BF16, BYTES_F32 = 2, 4


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes: required work only
# ---------------------------------------------------------------------------

def counts(cfg: dict, traffic: dict) -> dict:
    """Operations the forward pass requires a token (x3 with the backward
    pass; what remat, the experts' tiles and the kernels recompute does not
    count), part by part, and the operations and bytes of the parts that
    have a roofline share or a bytes bound of their own.

    The attention kernels (`attn_*`, under the names
    `layer_metrics/attn_roofline.py` reads) do the work of the published
    head sizes whatever form runs: QK^T contracts over nope + rope = 192
    channels and PV over 128, over the causal half, three times that with
    the backward pass; q, k and their gradients at heads x 192 a token and
    v, out and theirs at heads x 128, each read or written once in bf16.
    The trunk's layers and the prediction module's block are alike:
    `attention_layers` counts both. The routed experts' work is that of the
    pairs expected on the experts held, tokens x top_k x held / experts
    (`experts_flops_per_pair` lets a reader that knows the pairs a step
    really held count those instead), over the trunk's expert layers and the
    module's. The head multiplies by the vocabulary slice twice, the second
    time over the T - 1 positions of a sequence that have a target."""
    d = cfg["hidden_size"]
    t, tokens = traffic["seq_len"], traffic["batch"] * traffic["seq_len"]
    nh = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    n_layers = cfg["num_hidden_layers"]
    n_dense = cfg["first_k_dense_replace"]
    n_mtp = cfg["num_nextn_predict_layers"]
    n_attn = n_layers + n_mtp
    n_moe = n_layers - n_dense + n_mtp

    proj_fwd = 2 * (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * nh * qk
                    + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                    + cfg["kv_lora_rank"] * nh
                    * (cfg["qk_nope_head_dim"] + dv)
                    + nh * dv * d)
    attn_kernel_fwd = 2 * t * nh * (qk + dv) // 2
    attn_fwd = proj_fwd + attn_kernel_fwd
    dense_fwd = 2 * 3 * d * cfg["intermediate_size"]
    experts = cfg["n_routed_experts_published"]
    held, k = cfg["experts_held"][1], cfg["num_experts_per_tok"]
    pair_fwd = 2 * 3 * d * cfg["moe_intermediate_size"]
    shared_fwd = pair_fwd * cfg["n_shared_experts"]
    pairs = tokens * k * held / experts
    moe_fwd = 2 * d * experts + shared_fwd + pair_fwd * k * held / experts
    head_fwd = 2 * d * cfg["vocab_size"]
    mtp_head_fwd = head_fwd * (t - 1) / t
    eh_fwd = 2 * 2 * d * d
    mtp_fwd = n_mtp * (eh_fwd + attn_fwd + moe_fwd + mtp_head_fwd)
    fwd = (n_layers * attn_fwd + n_dense * dense_fwd
           + (n_layers - n_dense) * moe_fwd + head_fwd + mtp_fwd)

    # the grouped products' bytes, a layer: the held experts' three matrices
    # read in bf16 forward and backward, their gradients written in float32,
    # and a pair's row in and out, forward and backward
    expert_params = held * 3 * d * cfg["moe_intermediate_size"]
    experts_bytes = (expert_params * (2 * BYTES_BF16 + BYTES_F32)
                     + pairs * d * 4 * BYTES_BF16)
    q_dim, v_dim = nh * qk, nh * dv
    return {
        "tokens_per_step": tokens,
        "flops_per_token": 3 * fwd,
        "fwd_flops_per_token": {
            "mla_projections": proj_fwd, "attention_kernel": attn_kernel_fwd,
            "dense_mlp": dense_fwd, "moe": moe_fwd, "lm_head": head_fwd,
            "mtp": mtp_fwd},
        "attention_layers": n_attn,
        "attn_flops_per_step": 3 * attn_kernel_fwd * tokens * n_attn,
        # q, dq, k, dk at heads x 192; v, dv, out, d(out) at heads x 128
        "attn_bytes_per_step": ((4 * q_dim + 4 * v_dim) * tokens * n_attn
                                * BYTES_BF16),
        # q and k, heads x 192 each, written once forward; their two
        # cotangents read once backward
        "mla_assemble_bytes_per_step": (4 * q_dim * tokens * n_attn
                                        * BYTES_BF16),
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

def model_config(cfg: dict) -> "joyai_flash.JoyaiFlashConfig":
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("joyai_llm_flash: models/joyai_flash.py routes by "
                         "sigmoid scores with a selection bias (noaux_tc)")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("joyai_llm_flash: models/joyai_flash.py has no "
                         "group-limited routing (n_group, topk_group 1)")
    if cfg["rope_scaling"] is not None or cfg["attention_bias"]:
        raise ValueError("joyai_llm_flash: no rope scaling and no attention "
                         "bias are built")
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "intermediate_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_interleave", "num_experts_per_tok",
            "moe_intermediate_size", "n_shared_experts",
            "routed_scaling_factor", "norm_topk_prob",
            "num_nextn_predict_layers", "mtp_loss_weight", "rms_norm_eps",
            "initializer_range")
    return joyai_flash.JoyaiFlashConfig(
        rope_theta=float(cfg["rope_theta"]),
        n_routed_experts=cfg["n_routed_experts_published"],
        experts_held=tuple(cfg["experts_held"]),
        **{key: cfg[key] for key in same})


class System(nemotron3_nano.System):
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window."""

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import paddle_tpu as fluid
        from paddle_tpu.contrib import mixed_precision as mp

        if chips != 1 or traffic.get("layout", "single") != "single":
            raise ValueError("joyai_llm_flash runs on one chip, layout single")
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

        self._fluid, self._model = fluid, joyai_flash
        self._tokens = traffic["batch"] * traffic["seq_len"]
        self._k = cfg["num_experts_per_tok"]
        with fluid.unique_name.guard():     # the same names every build
            (self.main, self.startup, _, self.loss, self.counters,
             self.terms) = joyai_flash.build_pretrain_program(
                 model_config(cfg), traffic["batch"], traffic["seq_len"],
                 optimizer_factory=opt)
        self._fetch = ([self.loss]
                       + [v for _, tokens, pairs in self.counters
                          for v in (tokens, pairs)]
                       + [self.terms["mtp"]])
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.program = self.main

    def record(self, counts) -> None:
        joyai_flash.record_counters(self.counters, counts, self._tokens,
                                    self._k)


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
