"""nemotron3_nano: the system under test, and the counts its metrics need.

`build` goes through the program's public entry points only
(`models.nemotron_h.build_pretrain_program`, `Executor`); the one exception,
`hbm`, is the ERNIE adapter's (benchmark/program_access.py). The plain reference is beside
this file, in nemotron3_nano_reference.py, and imports none of this."""
from __future__ import annotations

from benchmark.configs import ernie_base
# at import, so that a tree without the model fails when the cell is loaded
# and not after the reference has run
from paddle_tpu.models import nemotron_h

BYTES_BF16, BYTES_F32 = 2, 4


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes: required work only
# ---------------------------------------------------------------------------

def counts(cfg: dict, traffic: dict) -> dict:
    """Operations the forward pass requires a token (x3 with the backward
    pass; what remat and the kernels recompute does not count), block by
    block, and the operations and bytes of the two parts that have a
    roofline share of their own: the state-space scan, the routed experts'
    grouped products and the attention kernels (`attn_*`, under the names
    `layer_metrics/attn_roofline.py` reads: QK^T and PV over the causal half,
    three times that with the backward pass; Q, O and their gradients at the
    query heads' width and K, V and theirs at the key/value heads', each
    read or written once in bf16).

    Attention and the positions of a scan chunk are causal: half of the
    [T, T] and of the [chunk, chunk] products is required. The routed
    experts' work is that of the pairs expected on the experts held,
    tokens x top_k x held / experts; `experts_flops_per_pair` lets a reader
    that knows the pairs a step really held count those instead."""
    d = cfg["hidden_size"]
    t, tokens = traffic["seq_len"], traffic["batch"] * traffic["seq_len"]
    pattern = cfg["hybrid_override_pattern"]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, chunk = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    d_inner, gn = heads * p, g * n
    conv_dim = d_inner + 2 * gn

    # the scan, a token and layer, forward: C B^T and (scores) x over half a
    # chunk, the chunk's state in, the entering state out
    ssd_fwd = (chunk * n * g + chunk * p * heads) + 2 * 2 * p * n * heads
    mamba_fwd = (2 * d * (d_inner + conv_dim + heads) + 2 * d_inner * d
                 + 2 * cfg["conv_kernel"] * conv_dim + ssd_fwd)
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    attn_fwd = (2 * d * (q_dim + 2 * kv_dim) + 2 * q_dim * d
                + 4 * t * q_dim // 2)
    experts = cfg["n_routed_experts_published"]
    held, k = cfg["experts_held"][1], cfg["num_experts_per_tok"]
    pair_fwd = 2 * 2 * d * cfg["moe_intermediate_size"]
    pairs = tokens * k * held / experts
    moe_fwd = (2 * d * experts
               + 2 * 2 * d * cfg["moe_shared_expert_intermediate_size"]
               + pair_fwd * k * held / experts)
    head_fwd = 2 * d * cfg["vocab_size"]
    per_kind = {"M": mamba_fwd, "*": attn_fwd, "E": moe_fwd}
    fwd = sum(per_kind[c] for c in pattern) + head_fwd
    n_mamba, n_moe = pattern.count("M"), pattern.count("E")
    n_attn = pattern.count("*")

    # the scan's bytes, a token and layer: x, B, C, dt in and y out, bf16,
    # forward; again with dy in and the four gradients out, backward
    ssd_io = (d_inner + 2 * gn + heads + d_inner) * BYTES_BF16
    # the grouped products' bytes, a layer: the held experts' two matrices
    # read in bf16 forward and backward, their gradients written in float32,
    # and a pair's row in and out, forward and backward
    expert_params = held * 2 * d * cfg["moe_intermediate_size"]
    experts_bytes = (expert_params * (2 * BYTES_BF16 + BYTES_F32)
                     + pairs * d * 4 * BYTES_BF16)
    return {
        "tokens_per_step": tokens,
        "flops_per_token": 3 * fwd,
        "fwd_flops_per_token": {"mamba": mamba_fwd, "attention": attn_fwd,
                                "moe": moe_fwd, "lm_head": head_fwd},
        "attn_flops_per_step": 3 * (4 * t * q_dim // 2) * tokens * n_attn,
        "attn_bytes_per_step": (4 * (q_dim + kv_dim) * tokens * n_attn
                                * BYTES_BF16),
        "ssd_flops_per_step": 3 * ssd_fwd * tokens * n_mamba,
        "ssd_bytes_per_step": 3 * ssd_io * tokens * n_mamba,
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

class _LossWithCounters:
    """A step's loss as the harness takes it (`np.asarray(loss)` at a
    block's end), with the counters the step fetched beside it."""

    def __init__(self, loss, counts, system):
        self._loss, self._counts, self._system = loss, counts, system

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        self._system.record(self._counts)
        return np.asarray(self._loss, dtype=dtype)


class System(ernie_base.System):
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window. What does
    not depend on the model (the first gradient's norms from Adam's first
    moment, the update's norms, `hbm`) is the ERNIE adapter's."""

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import paddle_tpu as fluid
        from paddle_tpu.contrib import mixed_precision as mp

        if chips != 1 or traffic.get("layout", "single") != "single":
            raise ValueError("nemotron3_nano runs on one chip, layout single")
        opt_cfg = cfg["optimizer"]
        self._beta1 = opt_cfg["beta1"]
        mcfg = nemotron_h.NemotronHConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            pattern=cfg["hybrid_override_pattern"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            mamba_num_heads=cfg["mamba_num_heads"],
            mamba_head_dim=cfg["mamba_head_dim"],
            ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
            conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
            time_step_min=cfg["time_step_min"],
            time_step_max=cfg["time_step_max"],
            time_step_floor=cfg["time_step_floor"],
            n_routed_experts=cfg["n_routed_experts_published"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            shared_intermediate_size=cfg[
                "moe_shared_expert_intermediate_size"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["norm_topk_prob"],
            experts_held=tuple(cfg["experts_held"]),
            norm_eps=cfg["norm_eps"],
            initializer_range=cfg["initializer_range"])

        def opt():
            adam = fluid.optimizer.Adam(
                opt_cfg["learning_rate"], beta1=opt_cfg["beta1"],
                beta2=opt_cfg["beta2"], epsilon=opt_cfg["epsilon"])
            if cfg["amp_dtype"] is None:      # float32, the CPU tests' preset
                return adam
            return mp.decorate(adam, dtype=cfg["amp_dtype"],
                               use_dynamic_loss_scaling=False)

        self._fluid, self._model = fluid, nemotron_h
        self._tokens = traffic["batch"] * traffic["seq_len"]
        self._k = cfg["num_experts_per_tok"]
        with fluid.unique_name.guard():     # the same names every build
            self.main, self.startup, _, self.loss, self.counters = (
                nemotron_h.build_pretrain_program(
                    mcfg, traffic["batch"], traffic["seq_len"],
                    optimizer_factory=opt))
        self._fetch = [self.loss] + [v for _, tokens, pairs in self.counters
                                     for v in (tokens, pairs)]
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.program = self.main

    def start(self, weights: dict) -> None:
        """Run the startup program (optimizer state, counters), then put the
        benchmark's seeded weights in the parameters' place."""
        with self._fluid.scope_guard(self.scope):
            self.exe.run(self.startup)
        for name, value in weights.items():
            if not self.scope.has_var(name):
                raise KeyError(f"the program has no parameter {name!r}")
            self.scope.set_var(name, value)
        # the routers' correction biases are weights no optimizer touches
        self._leaves = [k for k in weights if self.scope.has_var(
            f"{k}_AdamOptimizer_moment1")]

    def step(self, batch: dict):
        """Dispatch one training step; returns the loss, still on the device,
        with the expert blocks' counters beside it: where the caller brings
        the loss to the host (`np.asarray`), and only there, the counters
        of that step go into the program's registry."""
        self._last_feed = batch
        loss, *counts = self.exe.run(
            self.program, feed=batch, fetch_list=self._fetch,
            scope=self.scope, return_numpy=False)
        return _LossWithCounters(loss, counts, self)

    def record(self, counts) -> None:
        self._model.record_moe_counters(self.counters, counts, self._tokens,
                                        self._k)

    def update_norms(self, initial: dict) -> dict:
        """Of the leaves an optimizer touches."""
        return super().update_norms({k: initial[k] for k in self._leaves})


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
