"""MoE + expert parallelism (new capability — no reference analog; the
reference's sparse story is pserver embeddings, parameter_prefetch.cc).

Checks: the router's invariants, the dropless sort-and-segment layer against
a plain loop over the experts (values and gradients, all experts or a held
range, all tokens on one expert), single-device == expert-parallel outputs
and gradients on the 8-device CPU mesh, the static-graph layer. Every test
runs on both forms of the grouped product: the loops over tiles, and the
Pallas kernels through the interpreter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from paddle_tpu.ops.pallas_kernels import grouped_ffn
from paddle_tpu.parallel import moe


@pytest.fixture(autouse=True, params=["loop", "pallas"])
def grouped_form(request, monkeypatch):
    from paddle_tpu.ops import eager
    monkeypatch.setattr(grouped_ffn, "FORCE_PALLAS_INTERPRET",
                        request.param == "pallas")
    eager._jit_cache.clear()      # an eager op lowered on the other form
    return request.param


def _params(d=16, h=32, e=8, seed=0):
    gw, w1, b1, w2, b2 = moe.init_moe_params(jax.random.PRNGKey(seed), d, h, e)
    return gw, w1, b1 + 0.1, w2, b2 - 0.05


def _loop_over_experts(x, gw, w1, b1, w2, b2, k, first=0, act=jax.nn.gelu,
                       scoring="softmax", scale=1.0):
    """The layer written plainly: every held expert over every token,
    weighted by the token's (renormalised) score for it, 0 where it was not
    among the token's k."""
    logits = x @ gw
    s = (jax.nn.softmax(logits, -1) if scoring == "softmax"
         else jax.nn.sigmoid(logits))
    _, idx = jax.lax.top_k(s, k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * scale
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        out = act(x @ w1[e] + (0 if b1 is None else b1[e])) @ w2[e] \
            + (0 if b2 is None else b2[e])
        y = y + out * jnp.sum(jnp.where(idx == e + first, w, 0.0), -1)[:, None]
    return y


def test_router_invariants():
    d, e, n, k = 16, 8, 64, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    gw = jax.random.normal(jax.random.PRNGKey(2), (d, e)) * 0.2
    r = moe.route(x, gw, k=k)
    idx, w = np.asarray(r.idx), np.asarray(r.weight)
    assert idx.shape == w.shape == (n, k) and idx.dtype == np.int32
    assert (idx[:, 0] != idx[:, 1]).all() and idx.min() >= 0 and idx.max() < e
    # best first, renormalised to one
    assert (w[:, 0] >= w[:, 1]).all()
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    assert np.isfinite(float(r.aux_loss))
    # sigmoid scoring: the correction bias moves the choice, not the weights
    bias = jnp.zeros((e,)).at[5].set(10.0)
    rs = moe.route(x, gw, k=k, scoring="sigmoid", correction_bias=bias,
                   routed_scaling=2.5)
    assert (np.asarray(rs.idx)[:, 0] == 5).all()
    np.testing.assert_allclose(np.asarray(rs.weight).sum(-1), 2.5, rtol=1e-6)
    s5 = jax.nn.sigmoid(x @ gw)[:, 5]
    s_other = jnp.take_along_axis(jax.nn.sigmoid(x @ gw), rs.idx[:, 1:], -1)
    np.testing.assert_allclose(np.asarray(rs.weight[:, 0]),
                               np.asarray(2.5 * s5 / (s5 + s_other[:, 0])),
                               rtol=1e-5)


@pytest.mark.parametrize("held", [(0, 8), (2, 3)])
def test_dense_moe_matches_a_loop_over_the_experts(held, monkeypatch):
    monkeypatch.setattr(moe, "TILE", 16)     # several tiles an expert
    d, h, e, n = 16, 32, 8, 200
    p = _params(d, h, e)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    sl = slice(held[0], held[0] + held[1])

    def cut(p):
        return (p[0],) + tuple(a[sl] for a in p[1:])

    def run(x, p):
        return moe.moe_ffn(x, *cut(p), k=2, experts_held=held)

    out = run(x, p)
    ref = _loop_over_experts(x, *cut(p), k=2, first=held[0])
    np.testing.assert_allclose(np.asarray(out.y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # what the op counted is what the router chose
    idx = np.asarray(moe.route(x, p[0], k=2).idx)
    counts = np.bincount(idx.ravel(), minlength=e)[sl]
    assert np.array_equal(np.asarray(out.tokens_per_expert), counts)
    assert int(out.pairs_held) == counts.sum()
    g = jax.grad(lambda a: jnp.sum(jnp.sin(run(*a).y)))((x, p))
    g_ref = jax.grad(lambda a: jnp.sum(jnp.sin(_loop_over_experts(
        a[0], *cut(a[1]), k=2, first=held[0]))))((x, p))
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(g[1][0]).max()) > 0.0      # the router learns


def test_no_token_is_dropped_when_all_choose_one_expert(monkeypatch):
    monkeypatch.setattr(moe, "TILE", 8)
    d, h, e, n = 8, 16, 4, 100
    _, w1, b1, w2, b2 = _params(d, h, e, seed=3)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (n, d))) + 0.1
    gw = jnp.zeros((d, e)).at[:, 2].set(5.0)        # every token: expert 2
    out = moe.moe_ffn(x, gw, w1, b1, w2, b2, k=1)
    assert np.array_equal(np.asarray(out.tokens_per_expert), [0, 0, n, 0])
    assert int(out.pairs_held) == n
    one = jax.nn.gelu(x @ w1[2] + b1[2]) @ w2[2] + b2[2]
    np.testing.assert_allclose(np.asarray(out.y), np.asarray(one),
                               rtol=1e-5, atol=1e-5)
    # and a layer that holds none of the chosen experts adds nothing
    none = moe.moe_ffn(x, gw, w1[:2], b1[:2], w2[:2], b2[:2], k=1,
                       experts_held=(0, 2))
    assert int(none.pairs_held) == 0 and not np.asarray(none.y).any()


@pytest.mark.parametrize("ep", [4, 8])
def test_expert_parallel_matches_dense(ep):
    d, h, e = 16, 32, 8
    n = 8 * 16  # divisible by ep
    p = _params(d, h, e)
    x = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    mesh = Mesh(np.array(jax.devices()[:ep]), ("ep",))
    got = moe.moe_ffn_expert_parallel(x, *p, mesh, axis="ep", k=2)
    ref = moe.moe_ffn(x, *p, k=2)
    # whatever the routing: nothing is dropped, so the whole batch agrees
    np.testing.assert_allclose(np.asarray(got.y), np.asarray(ref.y),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(got.aux_loss), float(ref.aux_loss),
                               rtol=1e-5)
    assert np.array_equal(np.asarray(got.tokens_per_expert),
                          np.asarray(ref.tokens_per_expert))
    assert int(got.pairs_held) == int(ref.pairs_held) == 2 * n


def test_expert_parallel_grads_match_dense():
    d, h, e, ep = 8, 16, 4, 4
    n = 4 * 8
    p = _params(d, h, e, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(5), (n, d))
    mesh = Mesh(np.array(jax.devices()[:ep]), ("ep",))

    def loss_ep(a):
        out = moe.moe_ffn_expert_parallel(a[0], *a[1], mesh=mesh, axis="ep",
                                          k=1)
        return jnp.sum(out.y ** 2) + 0.1 * out.aux_loss

    def loss_dense(a):
        out = moe.moe_ffn(a[0], *a[1], k=1)
        return jnp.sum(out.y ** 2) + 0.1 * out.aux_loss

    g_ep = jax.grad(loss_ep)((x, p))
    g_dn = jax.grad(loss_dense)((x, p))
    for a, b in zip(jax.tree_util.tree_leaves(g_ep),
                    jax.tree_util.tree_leaves(g_dn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("axis", ["dp", None])
def test_data_parallel_shards_match_the_unsharded_layer(axis):
    """Each data shard's tokens through a held range of gated experts inside
    a shard_map (where the kernels are the form and GSPMD cannot split
    them): values, counts, the averaged load-balance loss and every
    gradient are the unsharded layer's."""
    d, h, e, held = 8, 16, 8, (2, 4)
    n = 4 * 8
    gw, w1, b1, w2, b2, w3 = moe.init_moe_params(
        jax.random.PRNGKey(3), d, h, e, gated=True)
    p = (gw, w1[2:6], b1[2:6] + 0.1, w2[2:6], b2[2:6] - 0.05)
    x = jax.random.normal(jax.random.PRNGKey(6), (n, d))
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    kw = dict(k=2, act=jax.nn.silu, experts_held=held, scoring="sigmoid",
              routed_scaling=2.5)

    def loss(f, *extra):
        def scalar(a):
            out = f(a[0], *a[1], *extra, w3=a[2], **kw)
            return jnp.sum(out.y ** 2) + 0.1 * out.aux_loss, out
        return jax.value_and_grad(scalar, has_aux=True)((x, p, w3[2:6]))

    (_, got), g_dp = loss(moe.moe_ffn_data_parallel, mesh, axis)
    (_, ref), g_dn = loss(moe.moe_ffn)
    np.testing.assert_allclose(np.asarray(got.y), np.asarray(ref.y),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(got.aux_loss), float(ref.aux_loss),
                               rtol=1e-5)
    assert np.array_equal(np.asarray(got.tokens_per_expert),
                          np.asarray(ref.tokens_per_expert))
    assert int(got.pairs_held) == int(ref.pairs_held)
    for a, b in zip(jax.tree_util.tree_leaves(g_dp),
                    jax.tree_util.tree_leaves(g_dn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_moe_under_jit_train_step():
    """One Adam-style step of a tiny MoE block, jitted over the ep mesh."""
    import optax  # baked in

    d, h, e, ep, n = 8, 16, 8, 8, 64
    params = _params(d, h, e, seed=9)
    mesh = Mesh(np.array(jax.devices()[:ep]), ("ep",))
    x = jax.random.normal(jax.random.PRNGKey(6), (n, d))
    opt = optax.adam(1e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state, x):
        def loss_fn(p):
            out = moe.moe_ffn_expert_parallel(x, *p, mesh=mesh, axis="ep",
                                              k=2)
            return jnp.mean((out.y - x) ** 2) + 0.01 * out.aux_loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        upd, state = opt.update(grads, state)
        return optax.apply_updates(params, upd), state, loss

    p1, s1, l1 = step(params, state, x)
    p2, s2, l2 = step(p1, s1, x)
    assert np.isfinite(float(l1)) and np.isfinite(float(l2))
    assert float(l2) < float(l1)


def test_moe_layer_static_graph_trains():
    """layers.moe_ffn in a static program: trains dense, loss decreases, and
    the counters come out with the loss."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16])
        y = layers.data("y", [16])
        h, aux, tokens, pairs = layers.moe_ffn(
            x, num_experts=4, hidden_size=32, k=2, return_counts=True)
        mse = layers.reduce_mean(layers.square(layers.elementwise_sub(h, y)))
        loss = layers.elementwise_add(mse, layers.scale(aux, scale=0.01))
        fluid.optimizer.Adam(0.01).minimize(loss)

    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(32, 16).astype("float32"),
            "y": rng.rand(32, 16).astype("float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        outs = [exe.run(main, feed=feed, fetch_list=[loss, tokens, pairs])
                for _ in range(15)]
    losses = [float(o[0]) for o in outs]
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
    assert outs[-1][1].shape == (4,) and outs[-1][1].sum() == 64
    assert int(outs[-1][2]) == 64               # nothing dropped, ever


def test_moe_layer_expert_parallel_matches_dense():
    """Same program compiled over an ep mesh == plain executor losses."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.parallel import make_mesh

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            main.random_seed = startup.random_seed = 7
            x = layers.data("x", [16])
            y = layers.data("y", [16])
            h, aux = layers.moe_ffn(x, num_experts=8, hidden_size=32, k=1)
            mse = layers.reduce_mean(
                layers.square(layers.elementwise_sub(h, y)))
            loss = layers.elementwise_add(mse, layers.scale(aux, scale=0.01))
            fluid.optimizer.SGD(0.05).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(1)
    feed = {"x": rng.rand(32, 16).astype("float32"),
            "y": rng.rand(32, 16).astype("float32")}

    main, startup, loss = build()
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        ref = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
               for _ in range(4)]

    main, startup, loss = build()
    mesh = make_mesh({"ep": 8})
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        prog = fluid.CompiledProgram(main).with_mesh(mesh, data_axis="ep")
        got = [float(exe.run(prog, feed=feed, fetch_list=[loss])[0])
               for _ in range(4)]

    # the mesh run routes every shard's tokens with the same router and drops
    # nothing, so the losses are the single-device run's
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("bias,expected", [(None, 5), (False, 3)])
def test_moe_layer_custom_param_attr_distinct_params(bias, expected):
    """A user-supplied param_attr must yield distinct parameters (a shared
    attr would alias them all under one name); `bias_attr=False` leaves the
    experts without biases; a held range sizes the experts' weights."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.param_attr import ParamAttr

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8])
        h, aux = layers.moe_ffn(x, num_experts=4, hidden_size=4,
                                param_attr=ParamAttr(name="moe0",
                                                     learning_rate=0.5),
                                bias_attr=bias, experts_held=(2, 2))
    params = {v.name: v for v in main.global_block().all_parameters()}
    assert len(params) == expected, sorted(params)
    assert tuple(params["moe0.gate"].shape) == (8, 4)
    assert tuple(params["moe0.w1"].shape) == (2, 8, 4)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        out = exe.run(main, feed={"x": np.zeros((4, 8), "float32")},
                      fetch_list=[h])
        assert out[0].shape == (4, 8)


@pytest.mark.parametrize("ep,bias", [(4, False), (8, False), (4, True)],
                         ids=["ep4", "ep8", "ep4-bias"])
def test_gated_expert_parallel_matches_the_unsharded_layer(ep, bias):
    """The gated form (`w3`) with the published sigmoid router and its
    normaliser's epsilon: values, counters and every gradient, the third
    matrix sharded over the axis like the other two."""
    d, h, e = 16, 24, 8
    n = 8 * 12
    gw, w1, b1, w2, b2, w3 = moe.init_moe_params(
        jax.random.PRNGKey(3), d, h, e, gated=True)
    b1, b2 = (b1 + 0.1, b2 - 0.05) if bias else (None, None)
    x = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    mesh = Mesh(np.array(jax.devices()[:ep]), ("ep",))
    kw = dict(k=2, act=jax.nn.silu, scoring="sigmoid")

    def sharded(x, gw, w1, w2, w3):
        return moe.moe_ffn_expert_parallel(x, gw, w1, b1, w2, b2, mesh,
                                           axis="ep", w3=w3, **kw)

    def whole(x, gw, w1, w2, w3):
        return moe.moe_ffn(x, gw, w1, b1, w2, b2, w3=w3, **kw)

    args = (x, gw, w1, w2, w3)
    got, ref = sharded(*args), whole(*args)
    np.testing.assert_allclose(np.asarray(got.y), np.asarray(ref.y),
                               rtol=2e-5, atol=2e-5)
    assert np.array_equal(np.asarray(got.tokens_per_expert),
                          np.asarray(ref.tokens_per_expert))
    assert int(got.pairs_held) == int(ref.pairs_held) == 2 * n
    # not the plain layer under another name
    plain = moe.moe_ffn(x, gw, w1, b1, w2, b2, **kw)
    assert not np.allclose(np.asarray(plain.y), np.asarray(ref.y), atol=1e-3)
    ct = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    g_got = jax.grad(lambda *a: jnp.sum(sharded(*a).y * ct),
                     argnums=(0, 1, 2, 3, 4))(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(whole(*a).y * ct),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for a, b, name in zip(g_got, g_ref, ("x", "gate", "w1", "w2", "w3")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    assert float(jnp.abs(g_ref[4]).max()) > 0


def test_static_graph_layer_makes_the_third_matrix_of_gated_experts():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6, 16], dtype="float32")
        out, _ = layers.moe_ffn(
            x, 8, 24, k=2, act="silu", gated=True, bias_attr=False,
            experts_held=(2, 4), scoring="sigmoid",
            param_attr=fluid.ParamAttr(name="m"))
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert shapes == {"m.gate": (16, 8), "m.w1": (4, 16, 24),
                      "m.w2": (4, 24, 16), "m.w3": (4, 16, 24)}
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    value = np.random.RandomState(0).randn(2, 6, 16).astype("float32")
    (got,) = exe.run(main, feed={"x": value}, fetch_list=[out], scope=scope)
    p = {k: jnp.asarray(np.asarray(scope.find_var(k))) for k in shapes}
    want = moe.moe_ffn(jnp.asarray(value).reshape(12, 16), p["m.gate"],
                       p["m.w1"], None, p["m.w2"], None, k=2,
                       act=jax.nn.silu, experts_held=(2, 4),
                       scoring="sigmoid", w3=p["m.w3"])
    np.testing.assert_allclose(got.reshape(12, 16), np.asarray(want.y),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# top-8 of 256 with 16 held (ISSUE 39): the router at the width the new cell
# runs it, the held pairs against a literal loop over pairs
# ---------------------------------------------------------------------------

def _top8_of_256(n=48, d=16, h=8, seed=0, favour=None):
    """x, a 256-wide router, 16 held gated experts (16..31 of the layer).
    `favour` = (expert, shift): a bias on that expert's logit for every
    token (through an extra constant channel of x)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, d))
    gate = jax.random.normal(ks[1], (d, 256)) * 0.5
    if favour is not None:
        x = x.at[:, 0].set(1.0)
        gate = gate.at[0, favour[0]].set(favour[1])
    w1, w3 = (jax.random.normal(q, (16, d, h)) * 0.3 for q in ks[2:4])
    w2 = jax.random.normal(ks[4], (16, h, d)) * 0.3
    return x, gate, w1, w3, w2


def _literal_top8(x, gate, w1, w3, w2, first, scaling=2.5):
    """Sigmoid scores over all 256, the 8 largest chosen, their scores over
    their sum times `scaling`; a literal loop over the (token, expert) pairs
    adds the held ones."""
    scores = jax.nn.sigmoid(jnp.dot(x, gate, precision="highest"))
    _, idx = jax.lax.top_k(scores, 8)
    y = [jnp.zeros(x.shape[1])] * x.shape[0]
    counts = [0] * w1.shape[0]
    for n in range(x.shape[0]):
        chosen = [int(e) for e in idx[n]]
        total = sum(scores[n, e] for e in chosen)
        for e in chosen:
            j = e - first
            if 0 <= j < w1.shape[0]:
                hid = jax.nn.silu(x[n] @ w1[j]) * (x[n] @ w3[j])
                y[n] = y[n] + scores[n, e] / total * scaling * (hid @ w2[j])
                counts[j] += 1
    return jnp.stack(y), counts


@pytest.mark.parametrize("favour", [None, (20, 50.0), (20, -50.0)],
                         ids=["as_drawn", "one_expert_has_every_token",
                              "one_expert_has_none"])
def test_top8_of_256_with_16_held_against_a_literal_loop(favour):
    x, gate, w1, w3, w2 = _top8_of_256(favour=favour)
    first = 16

    def layer(x, gate, w1, w3, w2):
        return moe.moe_ffn(x, gate, w1, None, w2, None, k=8,
                           act=jax.nn.silu, experts_held=(first, 16),
                           scoring="sigmoid", routed_scaling=2.5, w3=w3)

    got = layer(x, gate, w1, w3, w2)
    want, counts = _literal_top8(x, gate, w1, w3, w2, first)
    np.testing.assert_allclose(got.y, want, rtol=2e-5, atol=2e-6)
    assert list(np.asarray(got.tokens_per_expert)) == counts
    assert int(got.pairs_held) == sum(counts) <= 48 * 8
    if favour == (20, 50.0):
        assert counts[4] == 48
    elif favour == (20, -50.0):
        assert counts[4] == 0 and sum(counts) > 0
    else:       # 16 of 256 held: about a sixteenth of the 384 pairs
        assert 8 < sum(counts) < 48
    ct = jax.random.normal(jax.random.PRNGKey(7), want.shape)
    grads = jax.grad(lambda *a: jnp.sum(layer(*a).y * ct),
                     argnums=(0, 1, 2, 3, 4))(x, gate, w1, w3, w2)
    lit = jax.grad(lambda *a: jnp.sum(_literal_top8(*a, first)[0] * ct),
                   argnums=(0, 1, 2, 3, 4))(x, gate, w1, w3, w2)
    for g, w, name in zip(grads, lit, ("x", "gate", "w1", "w3", "w2")):
        np.testing.assert_allclose(g, w, rtol=5e-5, atol=5e-6, err_msg=name)
