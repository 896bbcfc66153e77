"""ouro_2_6b: the system under test, and the counts its metrics need.

`build` goes through the program's public entry points only
(`models.ouro.build_pretrain_program`, `Executor`); the one exception, `hbm`,
is the ERNIE adapter's (benchmark/program_access.py). The plain reference is
beside this file, in ouro_2_6b_reference.py, and imports none of this."""
from __future__ import annotations

from benchmark.configs import ernie_base
from benchmark.configs.nemotron3_nano import _LossWithCounters
# at import, so that a tree without the model fails when the cell is loaded
# and not after the reference has run
from paddle_tpu.models import ouro

BYTES_BF16 = 2


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes: required work only
# ---------------------------------------------------------------------------

def counts(cfg: dict, traffic: dict) -> dict:
    """Operations the forward pass requires a token (x3 with the backward
    pass; what remat and the kernels recompute does not count): every
    application of a layer counts, `total_ut_steps` x `num_hidden_layers` of
    them, and the head once an exit. Attention is causal: half of the [T, T]
    products is required. The exit gate (three products of 2,048 a token)
    is left out. `attn_*` are under the names `layer_metrics/
    attn_roofline.py` reads: QK^T and PV over the causal half, three times
    that with the backward pass; Q, K, V, O and their gradients each read or
    written once in bf16."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    t, tokens = traffic["seq_len"], traffic["batch"] * traffic["seq_len"]
    passes = cfg["total_ut_steps"]
    applications = passes * cfg["num_hidden_layers"]
    layer_fwd = 2 * (d * (qd + 2 * kvd) + qd * d + 3 * d * f)
    attn_fwd = 4 * t * qd // 2
    head_fwd = 2 * d * cfg["vocab_size"]
    fwd = applications * (layer_fwd + attn_fwd) + passes * head_fwd
    return {
        "tokens_per_step": tokens,
        "flops_per_token": 3 * fwd,
        "fwd_flops_per_token": {"layers": applications * layer_fwd,
                                "attention": applications * attn_fwd,
                                "lm_head": passes * head_fwd},
        "applications": applications,
        "attn_flops_per_step": 3 * attn_fwd * tokens * applications,
        "attn_bytes_per_step": (4 * (qd + kvd) * tokens * applications
                                * BYTES_BF16),
    }


def work_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def model_config(cfg: dict) -> "ouro.OuroConfig":
    return ouro.OuroConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        total_ut_steps=cfg["total_ut_steps"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        entropy_beta=cfg["entropy_beta"],
        initializer_range=cfg["initializer_range"])


class System(ernie_base.System):
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window. What does
    not depend on the model (`start`, the first gradient's norms from Adam's
    first moment, the update's norms, `hbm`) is the ERNIE adapter's."""

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import paddle_tpu as fluid
        from paddle_tpu.contrib import mixed_precision as mp

        if chips != 1 or traffic.get("layout", "single") != "single":
            raise ValueError("ouro_2_6b runs on one chip, layout single")
        if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
            raise ValueError("ouro_2_6b: models/ouro.py has as many "
                             "key/value heads as query heads")
        opt_cfg = cfg["optimizer"]
        self._beta1 = opt_cfg["beta1"]

        def opt():
            adam = fluid.optimizer.Adam(
                opt_cfg["learning_rate"], beta1=opt_cfg["beta1"],
                beta2=opt_cfg["beta2"], epsilon=opt_cfg["epsilon"])
            if cfg["amp_dtype"] is None:      # float32, the CPU tests' preset
                return adam
            return mp.decorate(adam, dtype=cfg["amp_dtype"],
                               use_dynamic_loss_scaling=False)

        self._fluid = fluid
        with fluid.unique_name.guard():     # the same names every build
            self.main, self.startup, _, self.loss, self.counters = (
                ouro.build_pretrain_program(
                    model_config(cfg), traffic["batch"], traffic["seq_len"],
                    optimizer_factory=opt))
        self._fetch = [self.loss, *self.counters]
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.program = self.main

    def step(self, batch: dict):
        """Dispatch one training step; returns the loss, still on the device,
        with the loop's counters beside it: where the caller brings the loss
        to the host (`np.asarray`), and only there, the counters of that step
        go into the program's registry."""
        self._last_feed = batch
        loss, *counts = self.exe.run(
            self.program, feed=batch, fetch_list=self._fetch,
            scope=self.scope, return_numpy=False)
        return _LossWithCounters(loss, counts, self)

    def record(self, counts) -> None:
        ouro.record_loop_counters(*counts)


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
