"""Which tape entries the backward walk hands on behind an
`optimization_barrier` (`core/executor.py` `_walk_tape`): on one device every
entry that reads a trainable matrix, so its weight-gradient product is not
fused into the update that reads it; under a mesh that sums gradients across
devices only an entry that reads a parameter other entries read too. The
barrier changes no value: losses and updated parameters are the same to the
bit with it and without it. `executor/grad_barriers` counts the entries at
lowering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.contrib import mixed_precision as mp
from paddle_tpu.core import executor as ex
from paddle_tpu.core.program import unit
from paddle_tpu.observability import get_registry

BATCH, WIDTH = 8, 16


def _two_fc(x):
    h = layers.fc(x, 12, act="tanh", param_attr=fluid.ParamAttr(name="w0"))
    return layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w1"))


def _vectors_only(x):
    scale = layers.create_parameter([WIDTH], "float32", name="scale")
    shift = layers.create_parameter([WIDTH], "float32", name="shift",
                                    is_bias=True)
    return layers.elementwise_add(layers.elementwise_mul(x, scale), shift)


def _shared_fc(x):
    """One square matrix applied twice, and a matrix with one reader."""
    for _ in range(2):
        x = layers.fc(x, WIDTH, act="tanh", bias_attr=False,
                      param_attr=fluid.ParamAttr(name="loop.w"))
    return layers.fc(x, 1, bias_attr=False,
                     param_attr=fluid.ParamAttr(name="out.w"))


def _remat_fc(x):
    with unit("blk", remat=True):
        h = layers.fc(x, 12, act="tanh",
                      param_attr=fluid.ParamAttr(name="w0"))
        h = layers.fc(h, 12, act="tanh",
                      param_attr=fluid.ParamAttr(name="w1"))
    return layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w2"))


_OPTIMIZERS = {
    "sgd": lambda: fluid.optimizer.SGD(0.1),
    "momentum": lambda: fluid.optimizer.Momentum(0.1, 0.9),
    "adam": lambda: fluid.optimizer.Adam(1e-2),
}


def _build(body, optimizer="adam", amp=False, remat=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        main.random_seed = startup.random_seed = 11
        x = layers.data("x", [WIDTH])
        loss = layers.reduce_mean(layers.square(body(x)))
        opt = _OPTIMIZERS[optimizer]()
        if amp:
            opt = mp.decorate(opt, dtype="bfloat16")
        opt.minimize(loss)
    if remat:
        main.remat_policy = "full"
    return main, startup, loss


def _feeds(n, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.rand(BATCH, WIDTH).astype("float32")}
            for _ in range(n)]


def _parameters(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name))
            for p in main.global_block().all_parameters()}


def _lowered_barriers(main, startup, loss):
    """Barriers in the lowered text of the plain executor's step, and what
    the counter added while that step was lowered."""
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    names = sorted(v.name for v in main.list_vars()
                   if v.persistable and scope.has_var(v.name))
    state = {n: scope.find_var(n) for n in names}
    (feed,) = _feeds(1)
    step = exe._build(main, sorted(feed), [loss.name], names, names)
    counter = get_registry().counter("executor/grad_barriers")
    before = counter.value
    text = jax.jit(step._step).lower(
        state, {k: jnp.asarray(v) for k, v in feed.items()},
        scope.find_var("@RNG_STATE@")).as_text()
    return text.count("optimization_barrier"), counter.value - before


@pytest.mark.parametrize("body,entries", [
    (_two_fc, 2),           # a product a layer; the biases' adds hand on none
    (_vectors_only, 0),
    (_shared_fc, 3),        # two applications of one matrix, and the other
    (_remat_fc, 2),         # one entry holds the block's two matrices
])
def test_one_barrier_an_entry_that_reads_a_matrix(body, entries, monkeypatch):
    program = _build(body, remat=body is _remat_fc)
    lowered, counted = _lowered_barriers(*program)
    assert counted == entries
    # jax.checkpoint lowers barriers of its own: the walk's are what goes
    # when the walk's call does nothing
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    assert lowered - _lowered_barriers(*program)[0] == entries
    if body is not _remat_fc:
        assert lowered == entries


def barriers_a_walk(run, data_devices, monkeypatch):
    """What `executor/grad_barriers` adds over `run()`, by backward walk (a
    mesh path may stage its step more than once), every walk of which saw a
    data axis over `data_devices` devices."""
    seen, walk = [], ex._walk_tape

    def counting(op, env, ctx):
        seen.append(ctx.mesh.shape[ctx.data_axis])
        return walk(op, env, ctx)

    counter = get_registry().counter("executor/grad_barriers")
    before = counter.value
    with monkeypatch.context() as patch:
        patch.setattr(ex, "_walk_tape", counting)
        run()
    assert seen and set(seen) == {data_devices}
    return (counter.value - before) / len(seen)


def _mesh_barriers(body, devices, monkeypatch):
    main, startup, loss = _build(body)
    program = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=devices)
    exe = fluid.Executor(fluid.TPUPlace())

    def run():
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            (feed,) = _feeds(1)
            exe.run(program, feed=feed, fetch_list=[loss])

    return (barriers_a_walk(run, len(devices), monkeypatch),
            exe.compiled_step(program).as_text())


@pytest.mark.parametrize("body,entries", [
    (_two_fc, 0),
    (_vectors_only, 0),
    (_shared_fc, 2),        # the looped matrix keeps its barrier, as ever
])
def test_a_mesh_that_sums_gradients_lowers_the_looped_barriers_only(
        body, entries, monkeypatch):
    assert len(jax.devices()) == 8
    counted, text = _mesh_barriers(body, jax.devices(), monkeypatch)
    assert counted == entries
    assert ("all-reduce" in text) or ("reduce-scatter" in text)


def test_a_mesh_of_one_device_is_one_chip_to_the_rule(monkeypatch):
    """What excepts a mesh is that it sums gradients: a data axis of one
    device sums nothing, and the step gets the barriers of the plain path."""
    counted, _ = _mesh_barriers(_two_fc, jax.devices()[:1], monkeypatch)
    assert counted == 2


def _train(build, without_barrier, monkeypatch, batched=False):
    """Three steps' losses and the parameters after them."""
    counter = get_registry().counter("executor/grad_barriers")
    before = counter.value
    with monkeypatch.context() as patch:
        if without_barrier:
            patch.setattr(jax.lax, "optimization_barrier", lambda x: x)
        main, startup, loss = build()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            feeds = _feeds(4)
            losses = [exe.run(main, feed=feeds[0], fetch_list=[loss])[0]]
            if batched:
                losses.extend(exe.run_batched(
                    main, feeds[1:], fetch_list=[loss])[0])
            else:
                losses.extend(exe.run(main, feed=f, fetch_list=[loss])[0]
                              for f in feeds[1:])
            return (np.asarray(losses, np.float32).ravel(),
                    _parameters(main, scope), counter.value - before)


def _same_to_the_bit(build, monkeypatch, **how):
    with_, params, counted = _train(build, False, monkeypatch, **how)
    without, plain, _ = _train(build, True, monkeypatch, **how)
    assert counted > 0
    assert with_.tobytes() == without.tobytes()
    assert sorted(params) == sorted(plain)
    for name in params:
        assert params[name].tobytes() == plain[name].tobytes(), name
    assert np.all(np.isfinite(with_)) and len(set(with_.tolist())) == 4


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("optimizer", sorted(_OPTIMIZERS))
def test_the_barrier_changes_no_value(optimizer, amp, monkeypatch):
    _same_to_the_bit(lambda: _build(_two_fc, optimizer, amp), monkeypatch)


def test_the_barrier_changes_no_value_inside_a_remat_block(monkeypatch):
    _same_to_the_bit(lambda: _build(_remat_fc, remat=True), monkeypatch)


def test_the_barrier_changes_no_value_over_a_shared_weight(monkeypatch):
    _same_to_the_bit(lambda: _build(_shared_fc), monkeypatch)


def test_the_barrier_changes_no_value_under_run_batched(monkeypatch):
    _same_to_the_bit(lambda: _build(_two_fc), monkeypatch, batched=True)
