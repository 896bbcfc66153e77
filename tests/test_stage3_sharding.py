"""ShardingStrategy.stage3 (full-parameter FSDP) + the remat policy surface.

Stage3 extends the ZeRO annotations to the parameters themselves: every
trainable float leaf is NamedSharding'ed over the dp axis along its largest
dp-divisible dim (padded-boundary fallback for the rest), re-asserted
inside the step so uses become all-gathers and the update runs on the
shard. The contract under test: losses stay BITWISE identical to the
unsharded run, checkpoints round-trip across layouts, donation still
holds, and the remat policies ("none"/"minimal"/"full"/predicate) are
bitwise-neutral on dropout-free models.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid

from test_zero_sharding import DP, OPTS, _build, _compiled, _run


def _param_leaves(main, scope):
    out = {}
    for name, v in main.global_block().vars.items():
        if getattr(v, "trainable", False) and v.persistable:
            out[name] = (v, scope.find_var(name))
    return out


# -- parameter sharding ----------------------------------------------------

def test_stage3_param_shards_split_over_dp():
    """Every multi-element trainable leaf is sharded along its largest
    dp-divisible axis; non-divisible dim-0 leaves ride the padded
    boundary (global shape rounds up to a dp multiple)."""
    _, main, scope = _run(OPTS["adam"], fluid.ShardingStrategy.stage3)
    sharded = 0
    for name, (v, arr) in _param_leaves(main, scope).items():
        n = int(np.prod(tuple(v.shape) or (1,)))
        if n < DP:  # too small to split (e.g. a scalar-ish bias)
            continue
        shard = arr.addressable_shards[0].data
        # at least one dim must be cut to ~1/DP (padded leaves round up)
        fracs = [s / g for s, g in zip(shard.shape, arr.shape)]
        assert min(fracs) <= (1.0 / DP) + 1e-9, (name, shard.shape, v.shape)
        sharded += 1
    assert sharded >= 4  # zw0, zb0, zw1, zb1, zw2 are all >= DP elements


def test_stage3_padded_nondivisible_leaves():
    """(13,)-shaped leaves don't divide by 8: the boundary value is padded
    to 16, `_zero_padded` records the logical shape, and reading the leaf
    back through the program surface recovers the logical value."""
    _, main, scope = _run(OPTS["sgd"], fluid.ShardingStrategy.stage3)
    padded = getattr(main, "_zero_padded", {})
    assert padded.get("zb1") == (13,)
    assert padded.get("zw2") == (13, 1)
    arr = scope.find_var("zb1")
    assert arr.shape == (16,)  # padded global shape at the jit boundary
    # pad rows are zeros, real rows are finite and not all equal
    host = np.asarray(arr)
    assert np.all(host[13:] == 0)
    assert np.isfinite(host[:13]).all()


def test_stage3_scalar_leaf_replicated():
    _, main, scope = _run(OPTS["sgd"], fluid.ShardingStrategy.stage3)
    arr = scope.find_var("zb2")  # shape (1,) < DP
    assert arr.sharding.is_fully_replicated


# -- bitwise equivalence ---------------------------------------------------

@pytest.mark.parametrize("opt", ["sgd", "momentum", "adam"])
def test_stage3_losses_bitwise_vs_unsharded(opt):
    base, _, _ = _run(OPTS[opt], fluid.ShardingStrategy.off)
    s3, _, _ = _run(OPTS[opt], fluid.ShardingStrategy.stage3)
    assert base == s3  # byte-for-byte per step


def test_stage3_donation_preserved():
    """donate_argnums must keep working with param shardings in play — a
    dropped donation shows up as a jax 'donated buffer' warning."""
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _run(OPTS["adam"], fluid.ShardingStrategy.stage3)
    assert not [x for x in w if "donat" in str(x.message).lower()]


# -- checkpoint round-trip -------------------------------------------------

def test_stage3_checkpoint_roundtrip(tmp_path):
    """Save under stage3 (params gathered into the layout-independent
    bundle), restore into off / stage1 / stage3 — the next step is
    bitwise identical in every layout."""
    from paddle_tpu.parallel.checkpoint import (load_checkpoint,
                                                save_checkpoint)

    scope = fluid.Scope()
    main, startup, feed, loss = _build(OPTS["adam"])
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        prog = _compiled(main, loss, fluid.ShardingStrategy.stage3)
        for _ in range(3):
            exe.run(prog, feed=feed, fetch_list=[loss])
    save_checkpoint(str(tmp_path), 3, program=main, scope=scope,
                    blocking=True)
    # no per-shard files: every leaf fit the gather cap -> one bundle
    assert not [f for f in os.listdir(str(tmp_path)) if "shards" in f]
    with fluid.scope_guard(scope):
        cont = np.asarray(exe.run(prog, feed=feed,
                                  fetch_list=[loss])[0]).tobytes()

    for stage in (fluid.ShardingStrategy.off, fluid.ShardingStrategy.stage1,
                  fluid.ShardingStrategy.stage3):
        s2 = fluid.Scope()
        main2, startup2, feed2, loss2 = _build(OPTS["adam"])
        with fluid.scope_guard(s2):
            exe2 = fluid.Executor(fluid.TPUPlace())
            exe2.run(startup2)
            step = load_checkpoint(str(tmp_path), program=main2, scope=s2)
            assert step == 3
            prog2 = _compiled(main2, loss2, stage)
            got = np.asarray(exe2.run(prog2, feed=feed2,
                                      fetch_list=[loss2])[0]).tobytes()
        assert got == cont, f"restore into stage {int(stage)} diverged"


# -- remat policy surface --------------------------------------------------

def _unit_mlp(seed=3):
    """Dropout-free MLP whose hidden blocks are remat units."""
    rng = np.random.RandomState(seed)

    def attr(name, shape):
        from paddle_tpu.initializer import NumpyArrayInitializer
        w = (rng.rand(*shape).astype("float32") - 0.5) * 0.2
        return fluid.ParamAttr(name=name,
                               initializer=NumpyArrayInitializer(w))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1])
        h = x
        for i in range(3):
            with fluid.remat_unit(f"blk_{i}"):
                h = fluid.layers.fc(h, 32, act="tanh",
                                    param_attr=attr(f"rw{i}",
                                                    (h.shape[-1], 32)),
                                    bias_attr=attr(f"rb{i}", (32,)))
        out = fluid.layers.fc(h, 1, param_attr=attr("rwo", (32, 1)),
                              bias_attr=attr("rbo", (1,)))
        loss = fluid.layers.mean(fluid.layers.square(out - y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    rng = np.random.RandomState(5)
    feed = {"x": rng.rand(32, 16).astype("float32"),
            "y": rng.rand(32, 1).astype("float32")}
    return main, startup, feed, loss


def _run_policy(policy, stage=fluid.ShardingStrategy.off, steps=3):
    scope = fluid.Scope()
    main, startup, feed, loss = _unit_mlp()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        bs = fluid.BuildStrategy()
        bs.sharding_strategy = stage
        bs.remat_policy = policy
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)
        return [np.asarray(exe.run(prog, feed=feed,
                                   fetch_list=[loss])[0]).tobytes()
                for _ in range(steps)]


def test_remat_policies_bitwise_on_dropout_free_model():
    ref = _run_policy("none")
    assert _run_policy("minimal") == ref
    assert _run_policy("full") == ref


def test_remat_predicate_policy_bitwise():
    pred = lambda unit: "full" if unit.endswith("_1") else "minimal"  # noqa: E731
    assert _run_policy(pred) == _run_policy("none")


def test_remat_predicate_can_opt_units_out():
    assert _run_policy(lambda unit: False) == _run_policy("none")


def test_stage3_plus_full_remat_bitwise():
    assert (_run_policy("full", stage=fluid.ShardingStrategy.stage3)
            == _run_policy("none"))


def test_remat_policy_rejects_unknown_string():
    from paddle_tpu.core.compiler import resolve_remat
    with pytest.raises(ValueError):
        resolve_remat("everything")


def test_a_programs_own_remat_policy_is_taken_last():
    """`Program.remat_policy` (a model builder's request) is what
    resolve_remat takes where no strategy and no environment gives one."""
    from paddle_tpu.core.compiler import resolve_remat
    main = fluid.Program()
    assert main.remat_policy is None
    assert resolve_remat(program=main).token == ("none",)
    main.remat_policy = "full"
    assert resolve_remat(program=main).token == ("full", None)
    assert resolve_remat("minimal", program=main).token == ("minimal", None)
    assert main.clone().remat_policy == "full"


def test_remat_unit_attr_tagging():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [4])
        with fluid.remat_unit("u0"):
            h = fluid.layers.fc(x, 4, act="relu")
        fluid.layers.fc(h, 1)
    from paddle_tpu.core.program import remat_unit_of
    tagged = [remat_unit_of(op) for op in main.global_block().ops]
    assert "u0" in tagged            # ops inside the scope are tagged
    assert tagged[-1] is None        # ops outside are not


# -- int64 feed-warning dedup (bench-tail spam) ----------------------------

def test_no_per_step_warning_for_device_int64_feeds():
    """An already-on-device array fed into a declared-int64 slot must not
    re-trip jax's narrowing UserWarning on every step: the value already
    physically holds 32-bit data, only the REQUEST needed narrowing."""
    import jax.numpy as jnp

    from paddle_tpu.core.executor import convert_feed_value

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        fluid.layers.data("ids", [4], dtype="int64")
    block = main.global_block()
    val = jnp.arange(4, dtype=jnp.int32).reshape(1, 4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # ANY warning fails the test
        out = convert_feed_value(block, "ids", val)
    assert out.dtype == np.int32


# -- clean-interpreter smoke ----------------------------------------------

def test_stage3_smoke_subprocess(xla_8dev_subprocess_env):
    """CI smoke job: stage3-vs-off equivalence in a clean interpreter with
    XLA_FLAGS-forced 8 fake devices (zero_smoke_runner --stage3)."""
    runner = os.path.join(os.path.dirname(__file__), "zero_smoke_runner.py")
    proc = subprocess.run([sys.executable, runner, "--stage3"],
                          capture_output=True, text=True, timeout=300,
                          env=xla_8dev_subprocess_env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["device_count"] == DP
    assert report["losses_off"] == report["losses_stage3"]
    assert report["max_param_shard_frac"] <= (1.0 / DP) + 0.05
    assert report["state_bytes_stage3"] < report["state_bytes_off"]
