"""Core IR + executor tests (reference analogs: test_program.py,
test_executor_and_mul.py, test_backward.py)."""
import numpy as np
import pytest

import paddle_tpu as fluid


def test_program_build():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.fc(x, 3)
        assert y.name in main.global_block().vars
        assert len(main.all_parameters()) == 2  # w, b
        ops = [op.type for op in main.global_block().ops]
        assert "mul" in ops and "elementwise_add" in ops


def test_executor_feed_fetch():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.scale(x, scale=2.0, bias=1.0)
        exe = fluid.Executor(fluid.CPUPlace())
        xv = np.random.rand(3, 4).astype("float32")
        (out,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(out, xv * 2.0 + 1.0, rtol=1e-6)


def test_mul_fc_forward():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.fc(x, 3, bias_attr=False)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w_name = main.all_parameters()[0].name
        xv = np.random.rand(5, 4).astype("float32")
        out, wv = exe.run(main, feed={"x": xv}, fetch_list=[y, w_name])
    np.testing.assert_allclose(out, xv @ wv, rtol=1e-5)


def test_append_backward_grads():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.fc(x, 1, bias_attr=False)
        loss = fluid.layers.mean(y)
        params_grads = fluid.append_backward(loss)
        assert len(params_grads) == 1
        p, g = params_grads[0]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.ones((8, 4), dtype="float32")
        (gv,) = exe.run(main, feed={"x": xv}, fetch_list=[g])
    # d(mean(xw))/dw = mean over batch of x = ones/1 → each w grad = 1
    np.testing.assert_allclose(gv, np.ones((4, 1)), rtol=1e-5)


def test_gradients_api():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [3])
        y = fluid.layers.square(x)
        loss = fluid.layers.reduce_sum(y)
        (gx,) = fluid.gradients([loss], [x])
        exe = fluid.Executor(fluid.CPUPlace())
        xv = np.array([[1.0, 2.0, 3.0]], dtype="float32")
        (gv,) = exe.run(main, feed={"x": xv}, fetch_list=[gx])
    np.testing.assert_allclose(gv, 2 * xv, rtol=1e-6)


def test_stop_gradient_blocks_flow():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [3])
        x.stop_gradient = False
        frozen = fluid.layers.scale(x, scale=3.0)
        frozen.stop_gradient = True
        y = fluid.layers.elementwise_add(fluid.layers.square(x), frozen)
        loss = fluid.layers.reduce_sum(y)
        (gx,) = fluid.gradients([loss], [x])
        exe = fluid.Executor(fluid.CPUPlace())
        xv = np.array([[1.0, 2.0, 3.0]], dtype="float32")
        (gv,) = exe.run(main, feed={"x": xv}, fetch_list=[gx])
    # grad flows only through square branch: 2x (scale branch cut)
    np.testing.assert_allclose(gv, 2 * xv, rtol=1e-6)


def test_sgd_step_updates_param():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.fc(x, 1, bias_attr=False)
        loss = fluid.layers.mean(y)
        opt = fluid.optimizer.SGD(learning_rate=0.1)
        opt.minimize(loss)
        p = main.all_parameters()[0]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w0 = np.array(fluid.global_scope().find_var(p.name))
        xv = np.ones((2, 4), dtype="float32")
        exe.run(main, feed={"x": xv}, fetch_list=[loss])
        w1 = np.array(fluid.global_scope().find_var(p.name))
    np.testing.assert_allclose(w1, w0 - 0.1 * np.ones((4, 1)), rtol=1e-5)


def test_program_clone_for_test_freezes_dropout():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [10])
        y = fluid.layers.dropout(x, dropout_prob=0.5,
                                 dropout_implementation="upscale_in_train")
        test_prog = main.clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        xv = np.ones((4, 10), dtype="float32")
        (out_test,) = exe.run(test_prog, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(out_test, xv)


def test_rng_reproducible_across_programs():
    def run_once():
        main = fluid.Program()
        startup = fluid.Program()
        scope = fluid.Scope()
        with fluid.scope_guard(scope), fluid.program_guard(main, startup):
            w = fluid.layers.create_global_var([4, 4], 0.0, "float32", persistable=True,
                                               name="w")
            startup.global_block().create_var(name="seeded", shape=[4, 4],
                                              dtype="float32", persistable=True)
            startup.global_block().append_op(
                "gaussian_random", outputs={"Out": ["seeded"]},
                attrs={"shape": [4, 4], "dtype": "float32", "mean": 0.0, "std": 1.0})
            main.global_block().create_var(name="seeded", shape=[4, 4],
                                           dtype="float32", persistable=True)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return np.array(scope.find_var("seeded"))

    a = run_once()
    b = run_once()
    np.testing.assert_allclose(a, b)
    assert np.abs(a).sum() > 0


def test_gradients_multi_target():
    """calc_gradient parity (reference backward.py:820): several targets,
    per-target seed cotangents, contributions summed."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [3])
        t1 = fluid.layers.reduce_sum(fluid.layers.square(x))      # d/dx = 2x
        t2 = fluid.layers.reduce_sum(fluid.layers.scale(x, 3.0))  # d/dx = 3
        (gx,) = fluid.gradients([t1, t2], [x])
        exe = fluid.Executor(fluid.CPUPlace())
        xv = np.array([[1.0, 2.0, 3.0]], dtype="float32")
        (gv,) = exe.run(main, feed={"x": xv}, fetch_list=[gx])
    np.testing.assert_allclose(gv, 2 * xv + 3.0, rtol=1e-6)


def test_gradients_multi_target_seeded():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [3])
        t1 = fluid.layers.reduce_sum(fluid.layers.square(x))
        t2 = fluid.layers.reduce_sum(fluid.layers.scale(x, 3.0))
        seed = fluid.layers.fill_constant([1], "float32", 10.0)
        (gx,) = fluid.gradients([t1, t2], [x],
                                target_gradients=[None, seed])
        exe = fluid.Executor(fluid.CPUPlace())
        xv = np.array([[1.0, 2.0, 3.0]], dtype="float32")
        (gv,) = exe.run(main, feed={"x": xv}, fetch_list=[gx])
    np.testing.assert_allclose(gv, 2 * xv + 30.0, rtol=1e-6)


def test_gradients_wrt_intermediate_var():
    """Grad w.r.t. an op OUTPUT (not a leaf) must survive the non-SSA
    cotangent-consumption rule in the tape walk."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [3])
        h = fluid.layers.scale(x, 2.0)
        loss = fluid.layers.reduce_sum(h)
        (gh,) = fluid.gradients([loss], [h])
        exe = fluid.Executor(fluid.CPUPlace())
        (gv,) = exe.run(main, feed={"x": np.ones((1, 3), "float32")},
                        fetch_list=[gh])
    np.testing.assert_allclose(gv, np.ones((1, 3)))


def test_feed_validation_errors():
    """Bad feeds raise clear errors at feed time, not raw XLA errors inside
    the traced step (reference PrepareData-time checks, operator.cc:1031)."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        out = fluid.layers.fc(x, 2, bias_attr=False)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    with pytest.raises(ValueError, match="shape mismatch at dim 1"):
        exe.run(main, feed={"x": np.zeros((3, 5), "float32")},
                fetch_list=[out])
    with pytest.raises(ValueError, match="rank mismatch"):
        exe.run(main, feed={"x": np.zeros((3,), "float32")},
                fetch_list=[out])
    with pytest.raises(TypeError, match="cannot convert"):
        exe.run(main, feed={"x": object()}, fetch_list=[out])
    # correct feed still works
    got = exe.run(main, feed={"x": np.zeros((3, 4), "float32")},
                  fetch_list=[out])
    assert got[0].shape == (3, 2)


def test_state_var_shape_swap_falls_back_to_retrace():
    """Checkpoint surgery: swapping a persistable var for a DIFFERENT
    shape via scope.set_var must retrace (plain jit path), not crash the
    AOT executable — jax Format equality ignores shape, so the fast path
    needs its own shape check (review r4)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data("ids", [4], dtype="int64")
        emb = fluid.layers.embedding(ids, [8, 16],
                                     param_attr=fluid.ParamAttr(name="sw.emb"))
        loss = fluid.layers.reduce_mean(emb)
        fluid.optimizer.SGD(0.1).minimize(loss)
    feed = {"ids": np.zeros((2, 4), "int64")}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        l0 = float(exe.run(main, feed=feed, fetch_list=[loss])[0])
        exe.run(main, feed=feed, fetch_list=[loss])  # steady state
        # grow the vocab: same rank/dtype, new shape
        fluid.global_scope().set_var("sw.emb", np.zeros((32, 16), "float32"))
        l1 = float(exe.run(main, feed=feed, fetch_list=[loss])[0])
        assert np.isfinite(l1)
        grown = fluid.global_scope().find_var("sw.emb")
        assert tuple(np.asarray(grown).shape) == (32, 16)


def test_a_value_set_over_the_startup_s_is_the_only_one_alive():
    """Seeded weights put in a parameter's place (`scope.set_var`) free the
    startup program's draw: the startup step takes no state, so it has no
    leaf to know again on a later call and holds none of what it made
    (at Nemotron's size the held draw was 2.5 GiB of the chip)."""
    import gc
    import jax
    import jax.numpy as jnp

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [64])
        h = fluid.layers.fc(x, 1003, bias_attr=False,
                            param_attr=fluid.ParamAttr(name="leak.w"))
        loss = fluid.layers.reduce_mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)

    def alive():
        gc.collect()
        return sum(1 for a in jax.live_arrays()
                   if a.shape == (64, 1003) and not a.is_deleted())

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    before = alive()
    exe.run(startup, scope=scope)
    assert alive() == before + 1
    scope.set_var("leak.w", jnp.full((64, 1003), 0.5, jnp.float32))
    assert alive() == before + 1
    feed = {"x": np.ones((4, 64), "float32")}
    for _ in range(3):           # first call, then the steady path
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        assert alive() == before + 1
