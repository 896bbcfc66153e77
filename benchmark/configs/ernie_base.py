"""ernie_base: the system under test, and the counts its metrics need.

`build` goes through the program's public entry points only
(`models.bert.build_pretrain_program`, `Executor`,
`CompiledProgram.with_data_parallel`); the one exception, `hbm`, goes through
benchmark/program_access.py. The plain reference is beside this file, in
ernie_base_reference.py, and imports none of this."""
from __future__ import annotations

from benchmark import program_access

BYTES_BF16 = 2


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes
# ---------------------------------------------------------------------------

def counts(cfg: dict, traffic: dict) -> dict:
    """Operations the forward and backward passes require, from the shapes.
    The embedding gathers are not matrix work; attention is 4*T*H a token and
    layer forward (QK^T and PV), three times that with the backward pass; what
    the kernel recomputes does not count."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    layers, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    t, b = traffic["seq_len"], traffic["batch"]
    matmul_params = layers * (4 * h * h + 2 * h * f) + h * v
    attn_flops_per_token = 3 * 4 * t * h * layers
    flops_per_token = 6 * matmul_params + attn_flops_per_token
    tokens = b * t
    # attention kernels, per step and chip-set: Q, K, V, O and their four
    # gradients, each read or written once in bf16, per layer
    attn_bytes = layers * 8 * tokens * h * BYTES_BF16
    return {
        "tokens_per_step": tokens,
        "flops_per_token": flops_per_token,
        "attn_flops_per_step": attn_flops_per_token * tokens,
        "attn_bytes_per_step": attn_bytes,
        "attn_calls_per_step": 2 * layers,
    }


def work_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class System:
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window."""

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import jax

        import paddle_tpu as fluid
        from paddle_tpu.contrib import mixed_precision as mp
        from paddle_tpu.models import bert

        opt_cfg = cfg["optimizer"]
        self._beta1 = opt_cfg["beta1"]
        bcfg = bert.BertConfig(
            num_layers=cfg["num_hidden_layers"], hidden_size=cfg["hidden_size"],
            num_heads=cfg["num_attention_heads"],
            ffn_size=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
            max_position=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            hidden_dropout=cfg["hidden_dropout_prob"],
            attn_dropout=cfg["attention_probs_dropout_prob"],
            initializer_range=cfg["initializer_range"])

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
            self.main, self.startup, _, self.loss = (
                bert.build_pretrain_program(
                    bcfg, traffic["batch"], traffic["seq_len"],
                    optimizer_factory=opt))
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.program = self.main
        if traffic.get("layout", "single") == "data_parallel":
            self.program = fluid.CompiledProgram(self.main).with_data_parallel(
                loss_name=self.loss.name, places=jax.devices()[:chips])
        elif chips != 1:
            raise ValueError(f"layout 'single' runs on one chip, not {chips}")

    def start(self, weights: dict) -> None:
        """Run the startup program (optimizer state, counters), then put the
        benchmark's seeded weights in the parameters' place."""
        with self._fluid.scope_guard(self.scope):
            self.exe.run(self.startup)
        for name, value in weights.items():
            if not self.scope.has_var(name):
                raise KeyError(f"the program has no parameter {name!r}")
            self.scope.set_var(name, value)
        self._leaves = list(weights)

    def step(self, batch: dict):
        """Dispatch one training step; returns the loss, still on the device."""
        self._last_feed = batch
        (loss,) = self.exe.run(self.program, feed=batch,
                               fetch_list=[self.loss], scope=self.scope,
                               return_numpy=False)
        return loss

    def first_gradient_norms(self) -> dict:
        """After step one Adam's first moment is (1 - beta1) x the gradient the
        optimizer was given."""
        import jax
        import jax.numpy as jnp

        moments = {k: self.scope.find_var(f"{k}_AdamOptimizer_moment1")
                   for k in self._leaves}
        scale = 1.0 / (1.0 - self._beta1)
        norms = jax.jit(lambda t: {
            k: scale * jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in t.items()})(moments)
        return {k: float(v) for k, v in norms.items()}

    def update_norms(self, initial: dict) -> dict:
        import jax
        import jax.numpy as jnp

        now = {k: self.scope.find_var(k) for k in initial}
        norms = jax.jit(lambda a, b: {
            k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})(
                now, initial)
        return {k: float(v) for k, v in norms.items()}

    def hbm(self) -> dict:
        """XLA's own account of what the compiled step needs on one chip."""
        return program_access.memory_of(program_access.compiled_step(
            self.exe, self.program, self.scope, self._last_feed))


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
