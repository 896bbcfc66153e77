"""Every Pallas entry point a model can reach must lower for the TPU.

``jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic translation from a CPU-only host: block-spec and layout
rules, unsupported primitives and the "Mosaic kernels cannot be
automatically partitioned" refusal under a mesh all surface here (the
sparse-Adagrad row kernels removed in PR 21 failed exactly this check from
the day they were written). It is NOT a substitute for the chip: Mosaic
itself does not run, so VMEM limits, tiling and numerics are only proven by
``chip_smoke.py``, whose kernel cases (cut to batch 2) this test reuses.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
import paddle_tpu as fluid
from paddle_tpu.ops.pallas_kernels import fused_bn

fa = importlib.import_module("paddle_tpu.ops.pallas_kernels.flash_attention")


@pytest.fixture(autouse=True)
def _dispatch_as_on_tpu(monkeypatch):
    """Take the dispatch decisions a TPU host would take."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused_bn, "_on_tpu", lambda: True)


def _lower_for_tpu(fn, *args):
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    return jax.jit(fn).trace(*structs).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize(
    "case", chip_smoke.kernel_cases(batch=2), ids=lambda c: c.name)
def test_smoke_kernel_case_lowers_for_tpu(case):
    args = case.make_args(np.random.RandomState(0))
    assert "tpu_custom_call" in _lower_for_tpu(case.kernel, *args)


def test_flash_dropout_kernels_lower_for_tpu():
    """The in-kernel PRNG (pltpu.prng_*) path, forward and backward."""
    q = jnp.zeros((2, 512, 768), jnp.bfloat16)
    bias = jnp.zeros((2, 1, 512), jnp.float32)

    def loss(q, key):
        return jnp.sum(fa.flash_attention_packed(
            q, q, q, 12, bias=bias, dropout_rate=0.1,
            dropout_key=key).astype(jnp.float32))

    text = _lower_for_tpu(jax.grad(loss), q, jax.random.key(0))
    assert text.count("tpu_custom_call") >= 2


def test_data_parallel_flash_attention_lowers_for_tpu():
    """Under a mesh the lowering refuses a bare Mosaic call; the
    flash_attention op runs it per data shard inside a shard_map."""
    from paddle_tpu.core.executor import convert_feed_value
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(num_layers=1, hidden_size=128, num_heads=2,
                          ffn_size=256, vocab_size=100, max_position=128)
    main, startup, _, loss = bert.build_pretrain_program(
        cfg, 8, 128,
        optimizer_factory=lambda: fluid.optimizer.SGD(0.1))
    cp = fluid.CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    feed = {k: convert_feed_value(main.global_block(), k, v)
            for k, v in chip_smoke.ernie_feed(cfg, 8, 128).items()}
    names = sorted(v.name for v in main.list_vars()
                   if v.persistable and scope.has_var(v.name))
    step = cp._build(sorted(feed), [loss.name], names, names,
                     {k: v.ndim for k, v in feed.items()})
    text = step.trace(
        {n: scope.find_var(n) for n in names}, feed,
        jax.random.key(0)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
