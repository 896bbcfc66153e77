"""lfm2_24b_a2b: the system under test, and the counts its metrics need.

`build` goes through the program's public entry points only
(`models.lfm2.build_pretrain_program`, `Executor`); the one exception, `hbm`,
is the ERNIE adapter's (benchmark/program_access.py). What an adapter of a
model with expert counters does after it is built (`start` with the routers'
frozen biases, `step` with the counters fetched beside the loss, `record`,
`update_norms`) is the Nemotron adapter's `System`, taken by its public
name. The plain reference is beside this file, in
lfm2_24b_a2b_reference.py, and imports none of this."""
from __future__ import annotations

from benchmark.configs import nemotron3_nano
# at import, so that a tree without the model fails when the cell is loaded
# and not after the reference has run
from paddle_tpu.models import lfm2

BYTES_BF16, BYTES_F32 = 2, 4


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes: required work only
# ---------------------------------------------------------------------------

def counts(cfg: dict, traffic: dict) -> dict:
    """Operations the forward pass requires a token (x3 with the backward
    pass; what remat, the experts' tiles and the kernels recompute does not
    count), layer by layer, and the operations and bytes of the parts that
    have a roofline share or a bytes bound of their own: the attention
    kernels (`attn_*`, under the names `layer_metrics/attn_roofline.py`
    reads: QK^T and PV over the causal half, three times that with the
    backward pass; Q, O and their gradients at the query heads' width and K,
    V and theirs at the key/value heads', each read or written once in
    bf16), the routed experts' grouped products (three products a held
    pair: both halves of the gate and the output), and the convolution
    operator's elementwise part.

    The routed experts' work is that of the pairs expected on the experts
    held, tokens x top_k x held / experts; `experts_flops_per_pair` lets a
    reader that knows the pairs a step really held count those instead. The
    head multiplies by the vocabulary slice."""
    d = cfg["hidden_size"]
    t, tokens = traffic["seq_len"], traffic["batch"] * traffic["seq_len"]
    kinds = cfg["layer_types"]
    hd = d // cfg["num_attention_heads"]
    q_dim = cfg["num_attention_heads"] * hd
    kv_dim = cfg["num_key_value_heads"] * hd
    n_conv, n_attn = kinds.count("conv"), kinds.count("full_attention")
    n_dense = cfg["num_dense_layers"]
    n_moe = len(kinds) - n_dense

    conv_fwd = (2 * d * 3 * d + 2 * d * d + 2 * cfg["conv_L_cache"] * d
                + 2 * d)
    attn_kernel_fwd = 4 * t * q_dim // 2
    attn_fwd = 2 * d * (q_dim + 2 * kv_dim) + 2 * q_dim * d + attn_kernel_fwd
    dense_fwd = 2 * 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts_published"]
    held, k = cfg["experts_held"][1], cfg["num_experts_per_tok"]
    pair_fwd = 2 * 3 * d * cfg["moe_intermediate_size"]
    pairs = tokens * k * held / experts
    moe_fwd = 2 * d * experts + pair_fwd * k * held / experts
    head_fwd = 2 * d * cfg["vocab_size"]
    fwd = (n_conv * conv_fwd + n_attn * attn_fwd + n_dense * dense_fwd
           + n_moe * moe_fwd + head_fwd)

    # the grouped products' bytes, a layer: the held experts' three matrices
    # read in bf16 forward and backward, their gradients written in float32,
    # and a pair's row in and out, forward and backward
    expert_params = held * 3 * d * cfg["moe_intermediate_size"]
    experts_bytes = (expert_params * (2 * BYTES_BF16 + BYTES_F32)
                     + pairs * d * 4 * BYTES_BF16)
    # the gates and the filter between the two projections, a token and
    # layer: the [3D] in-projection's result read and the [D] gated result
    # written, forward; both read again with the [D] cotangent and the [3D]
    # gradient written, backward; bf16
    conv_gates_io = (3 * d + d + 3 * d + d + 3 * d) * BYTES_BF16
    return {
        "tokens_per_step": tokens,
        "flops_per_token": 3 * fwd,
        "fwd_flops_per_token": {"conv": conv_fwd, "attention": attn_fwd,
                                "dense_mlp": dense_fwd, "moe": moe_fwd,
                                "lm_head": head_fwd},
        "attn_flops_per_step": 3 * attn_kernel_fwd * tokens * n_attn,
        "attn_bytes_per_step": (4 * (q_dim + kv_dim) * tokens * n_attn
                                * BYTES_BF16),
        "experts_flops_per_pair": 3 * pair_fwd,
        "experts_pairs_per_step": pairs * n_moe,
        "experts_flops_per_step": 3 * pair_fwd * pairs * n_moe,
        "experts_bytes_per_step": experts_bytes * n_moe,
        "conv_gates_bytes_per_step": conv_gates_io * tokens * n_conv,
        "moe_blocks": n_moe,
        "pairs_routed_per_step": tokens * k * n_moe,
    }


def work_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def model_config(cfg: dict) -> "lfm2.Lfm2Config":
    return lfm2.Lfm2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=list(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        conv_L_cache=cfg["conv_L_cache"],
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        use_expert_bias=cfg["use_expert_bias"],
        experts_held=tuple(cfg["experts_held"]), norm_eps=cfg["norm_eps"],
        initializer_range=cfg["initializer_range"])


class System(nemotron3_nano.System):
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window."""

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import paddle_tpu as fluid
        from paddle_tpu.contrib import mixed_precision as mp

        if chips != 1 or traffic.get("layout", "single") != "single":
            raise ValueError("lfm2_24b_a2b runs on one chip, layout single")
        if cfg.get("conv_bias"):
            raise ValueError("lfm2_24b_a2b: models/lfm2.py has no bias on "
                             "the short convolution")
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

        self._fluid, self._model = fluid, lfm2
        self._tokens = traffic["batch"] * traffic["seq_len"]
        self._k = cfg["num_experts_per_tok"]
        with fluid.unique_name.guard():     # the same names every build
            self.main, self.startup, _, self.loss, self.counters = (
                lfm2.build_pretrain_program(
                    model_config(cfg), traffic["batch"], traffic["seq_len"],
                    optimizer_factory=opt))
        self._fetch = [self.loss] + [v for _, tokens, pairs in self.counters
                                     for v in (tokens, pairs)]
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.program = self.main


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
