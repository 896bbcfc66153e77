"""flash_attention correctness: blockwise/pallas path vs naive reference.

Mirrors the OpTest contract (SURVEY §4.1): numeric check of the op output vs
a dense numpy/jax reference, plus analytic-gradient checks of the custom_vjp
against jax.grad of the naive formulation."""
import numpy as np
import pytest


def _naive_attention(q, k, v, bias=None, causal=False):
    import jax.numpy as jnp

    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if bias is not None:
        s = s + bias
    if causal:
        t = q.shape[2]
        mask = np.tril(np.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = p / jnp.sum(p, -1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _rand(shape, seed):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_naive(causal):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention

    b, h, t, d = 2, 3, 64, 16
    q, k, v = (_rand((b, h, t, d), i) for i in range(3))
    ref = _naive_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           causal=causal)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


def test_flash_with_bert_style_mask():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention

    b, h, t, d = 2, 2, 32, 8
    q, k, v = (_rand((b, h, t, d), i) for i in range(3))
    # BERT mask: [B,1,1,T] additive, -1e4 at padded positions
    mask = np.zeros((b, 1, 1, t), np.float32)
    mask[:, :, :, t // 2:] = -1e4
    ref = _naive_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           bias=jnp.asarray(mask))
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          bias=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_naive(causal):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention

    b, h, t, d = 1, 2, 32, 8
    q, k, v = (jnp.asarray(_rand((b, h, t, d), i)) for i in range(3))
    mask = jnp.asarray(np.where(
        np.random.RandomState(9).rand(b, 1, 1, t) > 0.3, 0.0, -1e4
    ).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, bias=mask, causal=causal) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_naive_attention(q, k, v, bias=mask, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn in zip(g_flash, g_naive):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gn),
                                   rtol=1e-4, atol=1e-4)


def test_flash_dropout_deterministic_and_scaled():
    """Dropout path: same key → same output; mean magnitude preserved."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention

    b, h, t, d = 2, 2, 32, 8
    q, k, v = (jnp.asarray(_rand((b, h, t, d), i)) for i in range(3))
    key = jax.random.PRNGKey(7)
    o1 = flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key)
    o2 = flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = flash_attention(q, k, v, dropout_rate=0.3,
                         dropout_key=jax.random.PRNGKey(8))
    assert np.abs(np.asarray(o1) - np.asarray(o3)).max() > 1e-6
    # dropout on probs keeps outputs in the same ballpark (unbiased weights)
    o0 = flash_attention(q, k, v)
    assert np.abs(np.asarray(o1)).mean() == pytest.approx(
        np.abs(np.asarray(o0)).mean(), rel=0.5)
    # gradient through the dropout path works
    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_flash_attention_op_and_layer():
    """The registered op + layers.flash_attention through a real program."""
    import paddle_tpu as fluid

    b, h, t, d = 2, 2, 32, 8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data("q", shape=[h, t, d], dtype="float32")
        k = fluid.layers.data("k", shape=[h, t, d], dtype="float32")
        v = fluid.layers.data("v", shape=[h, t, d], dtype="float32")
        out = fluid.layers.flash_attention(q, k, v, is_test=True)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    qv, kv, vv = (_rand((b, h, t, d), i) for i in range(3))
    got = exe.run(main, feed={"q": qv, "k": kv, "v": vv},
                  fetch_list=[out.name])[0]
    import jax.numpy as jnp
    ref = _naive_attention(jnp.asarray(qv), jnp.asarray(kv), jnp.asarray(vv))
    np.testing.assert_allclose(np.asarray(ref), got, rtol=2e-5, atol=2e-5)


def test_bert_flash_matches_naive_path():
    """BERT encoder with use_flash_attention on/off gives the same loss
    (dropout disabled)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    losses = {}
    feed_cache = {}
    for flash in (False, True):
        cfg = bert.BertConfig(vocab_size=128, hidden_size=32, num_layers=1,
                              num_heads=2, ffn_size=64, max_position=32,
                              hidden_dropout=0.0, attn_dropout=0.0,
                              use_flash_attention=flash)
        main, startup, feeds, loss = bert.build_pretrain_program(
            cfg, 2, 16, optimizer_factory=None, is_test=True)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            if not feed_cache:
                rng = np.random.RandomState(0)
                feed_cache.update({
                    "src_ids": rng.randint(0, 128, (2, 16)).astype("int64"),
                    "pos_ids": np.tile(np.arange(16), (2, 1)).astype("int64"),
                    "sent_ids": np.zeros((2, 16), "int64"),
                    "input_mask": np.ones((2, 16), "float32"),
                    "mlm_labels": rng.randint(0, 128, (2, 16, 1)).astype("int64"),
                })
            losses[flash] = exe.run(main, feed=dict(feed_cache),
                                    fetch_list=[loss.name])[0]
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-4)


@pytest.mark.parametrize("causal,with_bias", [(False, False), (True, False),
                                              (False, True)])
@pytest.mark.parametrize("force_general", [False, True])
def test_pallas_kernel_interpret_mode(causal, with_bias, force_general,
                                      monkeypatch):
    """The actual Pallas kernels, run through the interpreter on CPU, against
    the naive reference — validates what executes on the real chip. At these
    single-block shapes the one-pass grouped kernel dispatches by default;
    force_general pins group=1 so the online-softmax _fwd_kernel keeps
    interpreter coverage too."""
    import jax.numpy as jnp
    import importlib
    fa_mod = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")
    if force_general:
        monkeypatch.setattr(fa_mod, "_pick_group", lambda *a, **k: 1)

    b, h, t, d = 1, 2, 256, 64
    bh = b * h
    q, k, v = (jnp.asarray(_rand((bh, t, d), i)) for i in range(3))
    bias = None
    bias4 = None
    if with_bias:
        mask = np.zeros((bh, 1, t), np.float32)
        mask[:, :, t // 3:] = -1e4
        bias = jnp.asarray(mask)
        bias4 = jnp.asarray(mask.reshape(b, h, 1, t))
    out, lse = fa_mod._flash_fwd_pallas(
        q, k, v, bias, 1.0 / np.sqrt(d), causal,
        fa_mod.DEFAULT_BLOCK_Q, fa_mod.DEFAULT_BLOCK_K, interpret=True)
    ref = _naive_attention(q.reshape(b, h, t, d), k.reshape(b, h, t, d),
                           v.reshape(b, h, t, d), bias=bias4, causal=causal)
    np.testing.assert_allclose(np.asarray(out).reshape(b, h, t, d),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)
    # lse must match dense logsumexp of the scores
    s = jnp.einsum("btd,bkd->btk", q, k) / np.sqrt(d)
    if bias is not None:
        s = s + bias
    if causal:
        tri = np.tril(np.ones((t, t), bool))
        s = jnp.where(tri[None], s, -1e30)
    ref_lse = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-5, atol=1e-5)


import jax  # noqa: E402  (used in interpret-mode lse check)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multiblock_full_bias(causal):
    """t=256 spans multiple K blocks (nk>1): exercises the online-softmax
    correction across blocks AND the dbias block reassembly, including the
    gradient w.r.t. a full trainable [B,H,T,T] bias (ALiBi-style)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention

    b, h, t, d = 1, 1, 256, 16
    q, k, v = (jnp.asarray(_rand((b, h, t, d), i)) for i in range(3))
    bias = jnp.asarray(0.1 * _rand((b, h, t, t), 7))

    def loss_flash(q, k, v, bias):
        return jnp.sum(flash_attention(q, k, v, bias=bias, causal=causal) ** 2)

    def loss_naive(q, k, v, bias):
        return jnp.sum(_naive_attention(q, k, v, bias=bias, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for gf, gn in zip(g_flash, g_naive):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gn),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal,bias_kind", [
    (False, "none"), (True, "none"), (False, "mask"), (True, "mask"),
    (False, "full"), (True, "full"),
])
@pytest.mark.parametrize("force_general", [False, True])
def test_pallas_backward_interpret_mode(causal, bias_kind, force_general,
                                        monkeypatch):
    """The Pallas backward kernels through the interpreter on CPU against
    the naive dense gradients. At t=256 the single-block shapes dispatch to
    the grouped one-pass kernels; force_general pins the group to 1 so the
    general dq and dk/dv kernels (incl. the col-bias accumulation) keep
    interpreter coverage too."""
    import importlib
    import jax
    import jax.numpy as jnp
    fa_mod = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")
    if force_general:
        monkeypatch.setattr(fa_mod, "_pick_group", lambda *a, **k: 1)

    b, h, t, d = 1, 2, 256, 64
    q, k, v = (jnp.asarray(_rand((b, h, t, d), i)) for i in range(3))
    bias = None
    if bias_kind == "mask":
        m = np.where(np.random.RandomState(9).rand(b, 1, 1, t) > 0.3,
                     0.0, -1e4).astype(np.float32)
        bias = jnp.asarray(m)
    elif bias_kind == "full":
        bias = jnp.asarray(0.1 * _rand((b, h, t, t), 7))

    def loss(fn):
        def f(q, k, v, *rest):
            bb = rest[0] if rest else bias
            return jnp.sum(fn(q, k, v, bias=bb, causal=causal) ** 2)
        return f

    argnums = (0, 1, 2, 3) if bias_kind == "full" else (0, 1, 2)
    args = (q, k, v, bias) if bias_kind == "full" else (q, k, v)

    fa_mod.FORCE_PALLAS_INTERPRET = True
    try:
        assert fa_mod._pallas_ok(t, d)
        g_pallas = jax.grad(loss(fa_mod.flash_attention), argnums)(*args)
        out_pallas = fa_mod.flash_attention(q, k, v, bias=bias, causal=causal)
    finally:
        fa_mod.FORCE_PALLAS_INTERPRET = False
    g_naive = jax.grad(loss(_naive_attention), argnums)(*args)
    out_naive = _naive_attention(q, k, v, bias=bias, causal=causal)
    np.testing.assert_allclose(np.asarray(out_pallas), np.asarray(out_naive),
                               rtol=2e-4, atol=2e-4)
    for gp, gn in zip(g_pallas, g_naive):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gn),
                                   rtol=2e-4, atol=2e-4)


def test_dropout_without_key_raises():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention

    q = jnp.zeros((1, 1, 32, 8))
    with pytest.raises(ValueError, match="dropout_key"):
        flash_attention(q, q, q, dropout_rate=0.1)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="in-kernel PRNG numerics need a real TPU")
def test_pallas_dropout_on_tpu():
    """On hardware: in-kernel dropout is deterministic per key, consistent
    between forward and backward, and statistically ≈ the requested rate."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention

    b, h, t, d = 2, 2, 256, 64
    q, k, v = (jnp.asarray(_rand((b, h, t, d), i)) for i in range(3))
    key = jax.random.PRNGKey(3)
    o1 = flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key)
    o2 = flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o0 = flash_attention(q, k, v)
    assert np.abs(np.asarray(o1)).mean() == pytest.approx(
        np.abs(np.asarray(o0)).mean(), rel=0.5)
    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, dropout_rate=0.3, dropout_key=key) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_multi_kblock(causal):
    """Gradients with t > block (nk > 1) exercise the online-softmax
    correction across K blocks and the dbias reassembly — the paths a
    single-block seq len never reaches (ADVICE r1). Runs the Pallas
    kernels through the interpreter; full [B,H,T,T] trainable bias
    included, compared against the naive attention gradient."""
    import importlib
    import jax
    import jax.numpy as jnp
    fa_mod = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")

    b, h, d = 1, 2, 64  # d must satisfy the _pallas_ok d%64 gate
    t = fa_mod.DEFAULT_BLOCK_Q * 2  # guarantees nq = nk = 2
    old = fa_mod.FORCE_PALLAS_INTERPRET
    fa_mod.FORCE_PALLAS_INTERPRET = True
    try:
        assert fa_mod._pallas_ok(t, d), "test must exercise the Pallas path"
        q, k, v = (jnp.asarray(_rand((b, h, t, d), i)) for i in range(3))
        bias = jnp.asarray(_rand((b, h, t, t), 7) * 0.5)

        def loss_flash(q, k, v, bias):
            o = fa_mod.flash_attention(q, k, v, bias=bias, causal=causal)
            return jnp.sum(o * jnp.cos(o))

        def loss_naive(q, k, v, bias):
            o = _naive_attention(q, k, v, bias=bias, causal=causal)
            return jnp.sum(o * jnp.cos(o))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gn = jax.grad(loss_naive, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for name, a, bb in zip(("dq", "dk", "dv", "dbias"), gf, gn):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), rtol=5e-4, atol=5e-4,
                err_msg=f"{name} mismatch at t={t} (multi-block)")
    finally:
        fa_mod.FORCE_PALLAS_INTERPRET = old


# ---------------------------------------------------------------------------
# block-sparse packed-segment attention (ISSUE 19)
# ---------------------------------------------------------------------------

def _seg_mask(q_seg, k_seg, causal):
    """Dense boolean visibility the compact descriptor must reproduce:
    same (non-pad) segment, optionally global-position causal."""
    m = ((q_seg[:, :, None] == k_seg[:, None, :])
         & (q_seg[:, :, None] > 0) & (k_seg[:, None, :] > 0))
    if causal:
        tq, tk = q_seg.shape[1], k_seg.shape[1]
        m = m & (np.arange(tk)[None, None, :] <= np.arange(tq)[None, :, None])
    return m


def _ref_sparse(q, k, v, nh, q_seg, k_seg, causal):
    """Dense-mask reference on the [B, T, H] packed layout; fully-masked
    query rows (pad) produce exactly 0, matching the kernel contract."""
    import jax.numpy as jnp

    b, tq, hd = q.shape
    tk = k.shape[1]
    d = hd // nh
    qh = q.reshape(b, tq, nh, d).transpose(0, 2, 1, 3)
    kh = k.reshape(b, tk, nh, d).transpose(0, 2, 1, 3)
    vh = v.reshape(b, tk, nh, d).transpose(0, 2, 1, 3)
    mask = jnp.asarray(_seg_mask(q_seg, k_seg, causal))[:, None]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d)
    s = jnp.where(mask, s, -1e30)
    p = jnp.where(mask, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(l, 1e-30), vh)
    return o.transpose(0, 2, 1, 3).reshape(b, tq, hd)


def _uneven_segs(b, t, rng, max_seg=4, pad_last=True):
    """Packed rows with uneven bucket boundaries; row b-1 gets a long pad
    tail, row 0 is entirely pad (a fully-masked query/key row)."""
    segs = np.zeros((b, t), np.int32)
    for i in range(1, b):
        pos = 0
        for sid in range(1, max_seg + 1):
            ln = int(rng.randint(3, max(4, t // max_seg)))
            if pos + ln > t or (sid == max_seg and pad_last and i == b - 1):
                break
            segs[i, pos:pos + ln] = sid
            pos += ln
    return segs


def _sparse_mod():
    import importlib
    return importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")


@pytest.mark.parametrize("causal", [False, True])
def test_sparse_matches_dense_reference(causal):
    """jax fallback path (blocks < 64): uneven buckets incl. a fully
    pad row, fwd + all three grads vs the dense boolean-mask reference."""
    import jax
    import jax.numpy as jnp
    fa = _sparse_mod()

    b, t, nh, d = 3, 48, 2, 16
    rng = np.random.RandomState(0)
    seg = _uneven_segs(b, t, rng)
    q, k, v = (jnp.asarray(_rand((b, t, nh * d), i)) for i in range(3))

    got = fa.flash_attention_packed_sparse(q, k, v, nh, seg, seg,
                                           causal=causal)
    ref = _ref_sparse(q, k, v, nh, seg, seg, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # pad queries: exactly zero, not just close
    assert not np.asarray(got)[0].any()

    dy = jnp.asarray(_rand((b, t, nh * d), 7))
    gg = jax.grad(lambda *a: jnp.sum(
        fa.flash_attention_packed_sparse(*a, nh, seg, seg, causal=causal)
        * dy), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        _ref_sparse(*a, nh, seg, seg, causal) * dy),
        argnums=(0, 1, 2))(q, k, v)
    for a, r, nm in zip(gg, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=2e-4, err_msg=nm)
        # grads flowing into the pad row are exactly zero
        assert not np.asarray(a)[0].any(), nm


@pytest.mark.parametrize("causal", [False, True])
def test_sparse_pallas_interpret_matches_reference(causal):
    """Pallas grid path (interpret mode, T=128 ≥ block minimum): fwd +
    grads vs the dense reference on uneven buckets."""
    import jax
    import jax.numpy as jnp
    fa = _sparse_mod()

    b, t, nh, d = 2, 128, 2, 64
    rng = np.random.RandomState(1)
    seg = _uneven_segs(b, t, rng, max_seg=3)
    q, k, v = (jnp.asarray(_rand((b, t, nh * d), i)) for i in range(3))

    fa.FORCE_PALLAS_INTERPRET = True
    try:
        assert fa._sparse_pallas_ok(t, t, d)
        got = fa.flash_attention_packed_sparse(q, k, v, nh, seg, seg,
                                               causal=causal)
        dy = jnp.asarray(_rand((b, t, nh * d), 9))
        gg = jax.grad(lambda *a: jnp.sum(
            fa.flash_attention_packed_sparse(*a, nh, seg, seg,
                                             causal=causal) * dy),
            argnums=(0, 1, 2))(q, k, v)
    finally:
        fa.FORCE_PALLAS_INTERPRET = False
    ref = _ref_sparse(q, k, v, nh, seg, seg, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    gr = jax.grad(lambda *a: jnp.sum(
        _ref_sparse(*a, nh, seg, seg, causal) * dy),
        argnums=(0, 1, 2))(q, k, v)
    for a, r, nm in zip(gg, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=2e-4, err_msg=nm)


@pytest.mark.parametrize("dropout", [0.0, 0.15])
def test_sparse_block_skip_is_bitwise_invisible(dropout, monkeypatch):
    """The whole point of the packed descriptor: skipping a fully-masked
    KV block must be BITWISE identical to processing it (the masked lanes
    contribute exact zeros). Compare computed block visibility vs a
    monkeypatched all-visible grid, fwd and bwd, with dropout on."""
    import jax
    import jax.numpy as jnp
    fa = _sparse_mod()

    b, t, nh, d = 2, 128, 2, 64
    rng = np.random.RandomState(2)
    seg = _uneven_segs(b, t, rng, max_seg=3)
    q, k, v = (jnp.asarray(_rand((b, t, nh * d), i)) for i in range(3))
    key = jax.random.PRNGKey(11) if dropout else None
    dy = jnp.asarray(_rand((b, t, nh * d), 5))

    def run():
        def loss(q, k, v):
            return jnp.sum(fa.flash_attention_packed_sparse(
                q, k, v, nh, seg, seg, causal=True,
                dropout_rate=dropout, dropout_key=key) * dy)
        out = fa.flash_attention_packed_sparse(
            q, k, v, nh, seg, seg, causal=True,
            dropout_rate=dropout, dropout_key=key)
        return (out,) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    fa.FORCE_PALLAS_INTERPRET = True
    try:
        skipping = run()
        monkeypatch.setattr(
            fa, "_compute_block_vis",
            lambda se, tq, tk, bq, bk, causal: jnp.ones(
                (se.shape[0], -(-tq // bq), -(-tk // bk)), jnp.int32))
        dense_grid = run()
    finally:
        fa.FORCE_PALLAS_INTERPRET = False
    for a, r, nm in zip(skipping, dense_grid, ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r),
                                      err_msg=nm)


def test_sparse_cross_attention_uneven_lengths():
    """Cross attention, Tq != Tk: decoder rows attend their own source
    segment only."""
    import jax
    import jax.numpy as jnp
    fa = _sparse_mod()

    b, tq, tk, nh, d = 2, 40, 56, 2, 16
    rng = np.random.RandomState(4)
    q_seg = _uneven_segs(b, tq, rng, max_seg=3)
    k_seg = _uneven_segs(b, tk, rng, max_seg=3)
    q = jnp.asarray(_rand((b, tq, nh * d), 0))
    k = jnp.asarray(_rand((b, tk, nh * d), 1))
    v = jnp.asarray(_rand((b, tk, nh * d), 2))

    got = fa.flash_attention_packed_sparse(q, k, v, nh, q_seg, k_seg)
    ref = _ref_sparse(q, k, v, nh, q_seg, k_seg, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    dy = jnp.asarray(_rand((b, tq, nh * d), 8))
    gg = jax.grad(lambda *a: jnp.sum(fa.flash_attention_packed_sparse(
        *a, nh, q_seg, k_seg) * dy), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        _ref_sparse(*a, nh, q_seg, k_seg, False) * dy),
        argnums=(0, 1, 2))(q, k, v)
    for a, r, nm in zip(gg, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=2e-4, err_msg=nm)


def test_sparse_dropout_deterministic_and_scaled():
    """Dropout keyed by logical block index: same key -> bitwise same,
    different key -> different, and the kept mass is 1/(1-rate) scaled."""
    import jax
    import jax.numpy as jnp
    fa = _sparse_mod()

    b, t, nh, d = 2, 48, 2, 16
    rng = np.random.RandomState(6)
    seg = _uneven_segs(b, t, rng)
    q, k, v = (jnp.asarray(_rand((b, t, nh * d), i)) for i in range(3))
    k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)

    a1 = fa.flash_attention_packed_sparse(q, k, v, nh, seg, seg,
                                          dropout_rate=0.3, dropout_key=k1)
    a2 = fa.flash_attention_packed_sparse(q, k, v, nh, seg, seg,
                                          dropout_rate=0.3, dropout_key=k1)
    a3 = fa.flash_attention_packed_sparse(q, k, v, nh, seg, seg,
                                          dropout_rate=0.3, dropout_key=k2)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    assert np.abs(np.asarray(a1) - np.asarray(a3)).max() > 1e-4
    with pytest.raises(ValueError):
        fa.flash_attention_packed_sparse(q, k, v, nh, seg, seg,
                                         dropout_rate=0.3)


def test_sparse_op_and_layer():
    """flash_attention_sparse as a program op: lowering matches the direct
    kernel call on the same inputs."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu import layers
    fa = _sparse_mod()

    b, t, nh, d = 2, 32, 2, 8
    rng = np.random.RandomState(5)
    seg = _uneven_segs(b, t, rng, max_seg=2)
    q, k, v = (_rand((b, t, nh * d), i) for i in range(3))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qv = layers.data("q", [t, nh * d])
        kv = layers.data("k", [t, nh * d])
        vv = layers.data("v", [t, nh * d])
        qs = layers.data("q_seg", [t], dtype="int32")
        ks = layers.data("k_seg", [t], dtype="int32")
        out = layers.flash_attention_sparse(qv, kv, vv, nh, qs, ks,
                                            causal=True)
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got = exe.run(main, feed={"q": q, "k": k, "v": v,
                                  "q_seg": seg, "k_seg": seg},
                      fetch_list=[out])[0]
    ref = fa.flash_attention_packed_sparse(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), nh, seg, seg,
        causal=True)
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the blocked kernels' tile schedule (ISSUE 36): a list of visited tiles, the
# dk/dv on transposed scores, compact statistics
# ---------------------------------------------------------------------------

def _fa_mod():
    import importlib
    return importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")


def _blocked_inputs(d, kv_group, bias_kind, t=512, bhq=4):
    import jax.numpy as jnp
    q, g = (jnp.asarray(_rand((bhq, t, d), i)) for i in (0, 3))
    k, v = (jnp.asarray(_rand((bhq // kv_group, t, d), i)) for i in (1, 2))
    bias = None
    if bias_kind == "col":
        bias = jnp.asarray(np.where(
            np.random.RandomState(9).rand(bhq, 1, t) > 0.3, 0.0,
            -1e4).astype(np.float32))
        bias = bias.at[:, :, 0].set(0.0)   # no row without a visible key
    elif bias_kind == "per_q":
        bias = jnp.asarray(0.5 * _rand((bhq, t, t), 7))
    return q, k, v, g, bias


def _blocked_pallas(fa, q, k, v, g, bias, causal, bq, bk):
    group = q.shape[0] // k.shape[0]
    scale = 1.0 / np.sqrt(q.shape[2])
    out, lse = fa._flash_fwd_pallas(q, k, v, bias, scale, causal, bq, bk,
                                    interpret=True, kv_group=group)
    dq, dk, dv, dbias = fa._flash_bwd_pallas(
        q, k, v, bias, g, lse, out, scale, causal, bq, bk, interpret=True,
        kv_group=group)
    return out, lse, dq, dk, dv, dbias


@pytest.mark.parametrize("bias_kind", ["none", "col", "per_q"])
@pytest.mark.parametrize("kv_group", [1, 4])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [False, True])
def test_blocked_kernels_match_blockwise_jax(causal, d, kv_group, bias_kind):
    """4 x 4 blocks through the interpreter: out, lse, dq, dk, dv and dbias
    of the scheduled kernels against the blockwise-JAX path."""
    import jax.numpy as jnp
    fa = _fa_mod()
    q, k, v, g, bias = _blocked_inputs(d, kv_group, bias_kind)
    t, bq = q.shape[1], 128
    got = _blocked_pallas(fa, q, k, v, g, bias, causal, bq, bq)
    scale = 1.0 / np.sqrt(d)
    kr, vr = fa._repeat_kv(k, kv_group), fa._repeat_kv(v, kv_group)
    out, lse = fa._flash_fwd_jax(q, kr, vr, bias, scale, causal, bq)
    dq, dk, dv, dbias = fa._flash_bwd_jax(
        (q, kr, vr, bias, None, out, lse), g, sm_scale=scale, causal=causal,
        block_k=bq, dropout_rate=0.0, has_bias=bias is not None)
    if bias_kind == "col":
        dbias = jnp.sum(dbias, axis=1, keepdims=True)
    want = (out, lse, dq, dk, dv, dbias)
    tols = (2e-5, 1e-5, 2e-4, 2e-4, 2e-4, 2e-4)
    for name, a, b, tol in zip(("out", "lse", "dq", "dk", "dv", "dbias"),
                               got, want, tols):
        if b is None:
            assert a is None
            continue
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                                   atol=tol, err_msg=name)


@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128)])
def test_blocked_kernels_with_unequal_blocks(bq, bk):
    """A diagonal tile is then visible on more than its own triangle, and a
    q block's last visible k block is not its own index."""
    fa = _fa_mod()
    q, k, v, g, _ = _blocked_inputs(64, 2, "none")
    got = _blocked_pallas(fa, q, k, v, g, None, True, bq, bk)
    want = _blocked_pallas(fa, q, k, v, g, None, True, 128, 128)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


@pytest.mark.parametrize("bias_kind", ["none", "per_q"])
def test_skipped_tiles_are_bitwise_invisible(monkeypatch, bias_kind):
    """A tile above the diagonal that the list leaves out would have
    changed nothing: with every tile of the square scheduled (and masked to
    -1e30 throughout) all outputs keep their bits, so the schedule decides
    what a call costs and nothing of what it computes."""
    fa = _fa_mod()
    q, k, v, g, bias = _blocked_inputs(64, 2, bias_kind)
    got = _blocked_pallas(fa, q, k, v, g, bias, True, 128, 128)
    monkeypatch.setattr(fa, "_tile_visible",
                        lambda iq, ik, bq, bk, window=None: ik >= 0)
    fa._tile_schedule.cache_clear()
    try:
        assert len(fa._tile_schedule(4, 4, 128, 128, True)[0]) == 16
        want = _blocked_pallas(fa, q, k, v, g, bias, True, 128, 128)
    finally:
        fa._tile_schedule.cache_clear()
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv", "dbias"), got,
                          want):
        if b is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b)), name


def _tile_gauges():
    from paddle_tpu.observability import get_registry
    return {(s["labels"]["kernel"], s["name"].split("/")[1]): s["value"]
            for s in get_registry().series()
            if s["name"].startswith("flash_attention/tiles_")}


def _clear_tile_gauges():
    from paddle_tpu.observability import get_registry
    for n in ("tiles_grid", "tiles_scheduled", "tiles_masked"):
        get_registry().remove_matching("flash_attention/" + n)


@pytest.mark.parametrize("causal,want", [(True, (256, 136, 136)),
                                         (False, (256, 256, 0))])
def test_tile_gauges_and_tables_built_once_a_shape(causal, want):
    """16 x 16 blocks, traced only: the gauges say what the schedule is, and
    a second call of the same shape builds no table."""
    import jax
    import jax.numpy as jnp
    fa = _fa_mod()
    bh, t, d, bq = 2, 2048, 64, 128
    x = jax.ShapeDtypeStruct((bh, t, d), jnp.float32)
    lse = jax.ShapeDtypeStruct((bh, t), jnp.float32)

    def trace():
        jax.eval_shape(lambda q: fa._flash_fwd_pallas(
            q, q, q, None, 0.125, causal, bq, bq, interpret=True), x)
        jax.eval_shape(lambda q, l: fa._flash_bwd_pallas(
            q, q, q, None, q, l, q, 0.125, causal, bq, bq, interpret=True),
            x, lse)

    _clear_tile_gauges()    # another kind of call's stay under their label
    trace()
    gauges = _tile_gauges()
    for kernel in ("fwd", "bwd"):
        got = tuple(gauges[kernel, n] for n in
                    ("tiles_grid", "tiles_scheduled", "tiles_masked"))
        assert got == want, (kernel, got)
    qi, ki = fa._tile_schedule(16, 16, bq, bq, causal)
    assert len(qi) == want[1] and qi.dtype == ki.dtype == np.int32
    # q-block-major for the forward, k-block-major for the backward
    assert (np.diff(qi) >= 0).all()
    assert (np.diff(fa._tile_schedule(16, 16, bq, bq, causal,
                                      k_major=True)[1]) >= 0).all()
    misses = fa._tile_schedule.cache_info().misses
    trace()
    assert fa._tile_schedule.cache_info().misses == misses


def test_causal_per_q_bias_schedules_the_square_for_dq_only():
    """A tile above the diagonal must still zero its block of the per-q
    bias gradient: such a call keeps a dq kernel of its own, which walks the
    whole square and runs a body on the triangle; forward and dk/dv walk the
    triangle."""
    fa = _fa_mod()
    assert len(fa._tile_schedule(4, 4, 128, 128, True)[0]) == 10
    assert len(fa._tile_schedule(4, 4, 128, 128, True,
                                 whole_square=True)[0]) == 16
    q, k, v, g, bias = _blocked_inputs(64, 1, "per_q")
    _clear_tile_gauges()
    *_, dbias = _blocked_pallas(fa, q, k, v, g, bias, True, 128, 128)
    assert {kernel for kernel, _ in _tile_gauges()} == {"fwd", "dq", "dkv"}
    above = np.triu(np.ones((512, 512), bool), 1)
    assert not np.asarray(dbias)[:, above].any()
    assert np.asarray(dbias)[:, ~above].any()


def test_tile_gauge_names_pass_the_metrics_lint():
    from paddle_tpu.tools import metrics_lint
    fa = _fa_mod()
    names = {n for t, n, _ in metrics_lint.scan_file(fa.__file__)
             if t == "gauge"}
    assert names == {"flash_attention/tiles_grid",
                     "flash_attention/tiles_scheduled",
                     "flash_attention/tiles_masked",
                     "flash_attention/scores_scheduled",
                     "flash_attention/scores_visible"}
    assert all(metrics_lint._LEGAL_RE.match(n) for n in names)


def test_long_sequences_take_blocks_of_1024(monkeypatch):
    """From four blocks of 1,024 a side the dense kernels' blocks are 1,024
    square (the block-sparse ones keep `_pick_blocks`); the public entry at
    T 4,096 then traces 4 x 4 tiles, 10 of them visited, and gives what
    the blockwise-JAX path gives."""
    import jax
    import jax.numpy as jnp
    fa = _fa_mod()
    assert [fa._pick_dense_blocks(t) for t in (8192, 4096, 5120)] == [
        (1024, 1024)] * 3
    for t in (3072, 2048, 1024, 512, 4096 + 512, 192):
        assert fa._pick_dense_blocks(t) == fa._pick_blocks(t)
    q, k, v, w = (jnp.asarray(_rand((1, 2 if i in (0, 3) else 1, 4096, 64),
                                    i)) for i in range(4))

    def run():
        return jax.value_and_grad(lambda *x: jnp.sum(fa.flash_attention(
            *x, causal=True) * w), (0, 1, 2))(q, k, v)

    want = run()
    monkeypatch.setattr(fa, "FORCE_PALLAS_INTERPRET", True)
    _clear_tile_gauges()
    got = run()
    gauges = _tile_gauges()
    for kernel in ("fwd", "bwd"):
        assert tuple(gauges[kernel, n] for n in (
            "tiles_grid", "tiles_scheduled", "tiles_masked")) == (16, 10, 10)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


# ---------------------------------------------------------------------------
# one backward kernel (ISSUE 40): the dk/dv kernel makes dq too, summed for the
# whole head in VMEM; a per-q bias or a head too long for that keeps the dq
# kernel beside it
# ---------------------------------------------------------------------------

def _bwd_pallas_calls(fa, q, k, v, g, bias, causal, bq):
    import jax
    lse = jax.ShapeDtypeStruct(q.shape[:2], np.float32)
    text = str(jax.make_jaxpr(lambda lse: fa._flash_bwd_pallas(
        q, k, v, bias, g, lse, g, 0.125, causal, bq, bq, interpret=True,
        kv_group=q.shape[0] // k.shape[0]))(lse))
    return text.count("pallas_call")


@pytest.mark.parametrize("blocks", [2, 4])
@pytest.mark.parametrize("bias_kind", ["none", "col"])
@pytest.mark.parametrize("d,dv", [(64, 64), (192, 128)])
@pytest.mark.parametrize("kv_group", [1, 4])
@pytest.mark.parametrize("causal", [False, True])
def test_one_backward_kernel(monkeypatch, causal, kv_group, d, dv, bias_kind,
                             blocks):
    """dq, dk, dv and the column bias's gradient of the one kernel against
    the blockwise-JAX path and against the two kernels it stands for, which
    sum a q block's dq over the same k blocks in the same order."""
    import jax.numpy as jnp
    fa = _fa_mod()
    bq = 128
    q, k, v, g, bias = _blocked_inputs(d, kv_group, bias_kind, t=blocks * bq)
    v, g = v[:, :, :dv], g[:, :, :dv]
    assert _bwd_pallas_calls(fa, q, k, v, g, bias, causal, bq) == 1
    got = _blocked_pallas(fa, q, k, v, g, bias, causal, bq, bq)
    scale = 1.0 / np.sqrt(d)
    kr, vr = fa._repeat_kv(k, kv_group), fa._repeat_kv(v, kv_group)
    dq, dk, dv_, dbias = fa._flash_bwd_jax(
        (q, kr, vr, bias, None, got[0], got[1]), g, sm_scale=scale,
        causal=causal, block_k=bq, dropout_rate=0.0,
        has_bias=bias is not None)
    if bias is not None:
        dbias = jnp.sum(dbias, axis=1, keepdims=True)
    # no head fits: the dq and dk/dv kernels
    monkeypatch.setattr(fa, "_DQ_HEAD_BUDGET", 0)
    assert _bwd_pallas_calls(fa, q, k, v, g, bias, causal, bq) == 2
    two = _blocked_pallas(fa, q, k, v, g, bias, causal, bq, bq)
    for name, a, jax_path, pair in zip(("dq", "dk", "dv", "dbias"), got[2:],
                                       (dq, dk, dv_, dbias), two[2:]):
        if jax_path is None:
            assert a is None and pair is None
            continue
        assert a.shape == jax_path.shape == pair.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(jax_path),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(pair),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("why", ["per_q_bias", "long_head"])
def test_what_keeps_the_two_backward_kernels(monkeypatch, why):
    """A per-q bias (its gradient is `ds` itself, tile by tile) and a head
    whose [T, d] float32 dq passes the VMEM budget: the call's shapes decide,
    and the gauges say which backward a shape took."""
    fa = _fa_mod()
    q, k, v, g, bias = _blocked_inputs(
        64, 1, "per_q" if why == "per_q_bias" else "none")
    if why == "long_head":
        assert fa._dq_in_dkv(8192, 192, "bfloat16", False)
        assert fa._dq_in_dkv(16384, 128, "bfloat16", False)
        assert not fa._dq_in_dkv(32768, 128, "bfloat16", False)
        # this call's head: 512 x 64 in float32
        monkeypatch.setattr(fa, "_DQ_HEAD_BUDGET",
                            fa._dq_head_bytes(512, 64, "float32") - 1)
    assert not fa._dq_in_dkv(512, 64, "float32", why == "per_q_bias")
    _clear_tile_gauges()
    assert _bwd_pallas_calls(fa, q, k, v, g, bias, True, 128) == 2
    assert {kernel for kernel, _ in _tile_gauges()} == {"dq", "dkv"}
    _clear_tile_gauges()
    assert _bwd_pallas_calls(fa, q, k, v, g, None, True, 128) == (
        2 if why == "long_head" else 1)
    assert {kernel for kernel, _ in _tile_gauges()} == (
        {"dq", "dkv"} if why == "long_head" else {"bwd"})


def test_the_kernels_labels_on_the_set_up_account():
    """`setup/kernel_traces{kernel}` counts a blocked backward as
    `flash_bwd`, and as `flash_bwd_dq` and `flash_bwd_dkv` where two run."""
    from paddle_tpu.observability import get_registry
    fa = _fa_mod()

    def traces():
        n = {}
        for s in get_registry().series():
            if s["name"] == "setup/kernel_traces":
                label = s["labels"]["kernel"]
                n[label] = n.get(label, 0) + s["value"]
        return n

    before = traces()
    q, k, v, g, bias = _blocked_inputs(64, 1, "per_q")
    _bwd_pallas_calls(fa, q, k, v, g, None, True, 128)
    _bwd_pallas_calls(fa, q, k, v, g, bias, True, 128)
    grew = {label: n - before.get(label, 0) for label, n in traces().items()
            if n != before.get(label, 0)}
    assert grew == {"flash_bwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}


# ---------------------------------------------------------------------------
# a value head size of its own (ISSUE 39): q and k share one head size, the
# one the scores contract over and the scale is taken from; v, out and their
# cotangents have another
# ---------------------------------------------------------------------------

def _plain_packed(q, k, v, nh, nkv, causal, sm_scale=None):
    """Softmax attention on packed [B, T, heads * size] tensors with the
    [T, T] scores written out; v's head size is its own."""
    import jax
    import jax.numpy as jnp
    b, t, _ = q.shape
    d, dv = q.shape[2] // nh, v.shape[2] // nkv
    heads = lambda x, n: jnp.repeat(
        x.reshape(b, t, n, -1).transpose(0, 2, 1, 3), nh // n, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", heads(q, nh), heads(k, nkv))
    s = s * (sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    if causal:
        s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), heads(v, nkv))
    return out.transpose(0, 2, 1, 3).reshape(b, t, nh * dv)


def _packed_inputs(b, t, nh, nkv, d, dv):
    import jax.numpy as jnp
    return tuple(jnp.asarray(_rand((b, t, width), i)) for i, width in
                 enumerate((nh * d, nkv * d, nkv * dv, nh * dv)))


# T 256 runs the one-pass kernels, 1,024 the blocked ones (two blocks a
# side); 192 / 128 are the published latent-attention sizes, 64 / 128 values
# wider than keys, and one case has grouped heads with it
VALUE_WIDTH_CASES = [(256, 2, 2, 192, 128), (1024, 2, 2, 192, 128),
                     (1024, 4, 1, 64, 128), (256, 8, 8, 128, 64),
                     (1024, 2, 2, 128, 192)]


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["blockwise_jax", "pallas_interpreted"])
@pytest.mark.parametrize("t,nh,nkv,d,dv", VALUE_WIDTH_CASES)
def test_value_head_size_of_its_own(monkeypatch, interpret, t, nh, nkv, d,
                                    dv):
    """Forward and all three gradients against plain attention, causal, on
    the path the CPU tests run and on the kernels through the interpreter:
    out and dv are `dv` wide, dq and dk `d` wide, the scale is d^-1/2."""
    import jax
    import jax.numpy as jnp
    fa = _fa_mod()
    monkeypatch.setattr(fa, "FORCE_PALLAS_INTERPRET", interpret)
    q, k, v, w = _packed_inputs(2, t, nh, nkv, d, dv)

    def run(f):
        return jax.value_and_grad(lambda *x: jnp.sum(f(*x) * w), (0, 1, 2))(
            q, k, v)

    got = run(lambda q, k, v: fa.flash_attention_packed(
        q, k, v, nh, causal=True, num_kv_heads=nkv))
    want = run(lambda q, k, v: _plain_packed(q, k, v, nh, nkv, True))
    assert [g.shape for g in got[1]] == [q.shape, k.shape, v.shape]
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)
    assert fa.flash_attention_packed(
        q, k, v, nh, causal=True, num_kv_heads=nkv).shape == (2, t, nh * dv)


def test_value_head_size_small_pair_not_causal_and_a_given_scale():
    """A small pair of sizes (24 / 16: the blockwise-JAX path, no kernel
    takes them), not causal, with the caller's scale and the 4D entry."""
    import jax.numpy as jnp
    fa = _fa_mod()
    q, k, v, _ = _packed_inputs(2, 64, 4, 4, 24, 16)
    for scale in (None, 0.3):
        got = fa.flash_attention_packed(q, k, v, 4, sm_scale=scale)
        np.testing.assert_allclose(
            got, _plain_packed(q, k, v, 4, 4, False, scale), rtol=2e-5,
            atol=2e-5)
    split = lambda x, n: x.reshape(2, 64, n, -1).transpose(0, 2, 1, 3)
    got4 = fa.flash_attention(split(q, 4), split(k, 4), split(v, 4))
    assert got4.shape == (2, 4, 64, 16)
    np.testing.assert_allclose(
        got4.transpose(0, 2, 1, 3).reshape(2, 64, 64),
        _plain_packed(q, k, v, 4, 4, False), rtol=2e-5, atol=2e-5)
    assert not fa._pallas_ok(1024, 128, 24) and not fa._pallas_ok(1024, 24)


def test_value_head_size_refusals_say_what_holds():
    import jax.numpy as jnp
    fa = _fa_mod()
    q, k, v, _ = _packed_inputs(1, 64, 4, 2, 16, 8)
    with pytest.raises(ValueError, match="q's head size 16"):
        fa.flash_attention_packed(q, k[:, :, :24], v, 4, num_kv_heads=2)
    with pytest.raises(ValueError, match="its own head size"):
        fa.flash_attention_packed(q, k, v[:, :, :15], 4, num_kv_heads=2)
    with pytest.raises(ValueError, match="k have q's head size"):
        fa.flash_attention(jnp.zeros((1, 2, 8, 16)), jnp.zeros((1, 2, 8, 8)),
                           jnp.zeros((1, 2, 8, 8)))
    seg = jnp.ones((1, 64), jnp.int32)
    with pytest.raises(ValueError, match="one head size for q, k and v"):
        fa.flash_attention_packed_sparse(q, q, q[:, :, :32], 4, seg, seg)


def test_the_layer_takes_the_value_width_from_v_s_shape():
    """`layers.flash_attention` says the value width by v's shape, packed
    and 4D, and its result's shape follows; the op runs it."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = layers.data("q", [64, 4 * 24], dtype="float32")
        k = layers.data("k", [64, 2 * 24], dtype="float32")
        v = layers.data("v", [64, 2 * 16], dtype="float32")
        out = layers.flash_attention(q, k, v, causal=True, num_heads=4,
                                     num_kv_heads=2)
        q4 = layers.data("q4", [4, 64, 24], dtype="float32")
        v4 = layers.data("v4", [4, 64, 16], dtype="float32")
        out4 = layers.flash_attention(q4, q4, v4)
    assert tuple(out.shape[1:]) == (64, 4 * 16)
    assert tuple(out4.shape[1:]) == (4, 64, 16)
    qv, kv, vv, _ = _packed_inputs(2, 64, 4, 2, 24, 16)
    exe = fluid.Executor(fluid.TPUPlace())
    (got,) = exe.run(main, feed={
        "q": np.asarray(qv), "k": np.asarray(kv), "v": np.asarray(vv),
        "q4": np.zeros((2, 4, 64, 24), "float32"),
        "v4": np.zeros((2, 4, 64, 16), "float32")}, fetch_list=[out])
    np.testing.assert_allclose(got, _plain_packed(qv, kv, vv, 4, 2, True),
                               rtol=2e-5, atol=2e-5)


def test_equal_head_sizes_trace_to_the_parent_s_kernels(monkeypatch):
    """Where q, k and v have one head size the calls are the ones they were
    before there were two: the same kernels with [block, d] blocks and
    accumulators, and a jaxpr of a known digest, with the addresses of
    objects taken out (PR 39 recorded the parent's, commit e7757d0, from a
    copy of that commit). A PR that changes the kernels on purpose records
    its own: PR 40's, whose backward is one kernel, so two calls for
    three."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    fa = _fa_mod()
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    digests = {}
    for name, (b, t, nh, nkv, d) in {
            "t8192_h32on8_d64": (2, 8192, 32, 8, 64),
            "t8192_h32on2_d128": (2, 8192, 32, 2, 128),
            "t4096_h16_d128": (2, 4096, 16, 16, 128)}.items():
        def loss(q, k, v):
            return jnp.sum(fa.flash_attention_packed(
                q, k, v, nh, causal=True,
                num_kv_heads=nkv).astype(jnp.float32))
        args = [jax.ShapeDtypeStruct((b, t, n * d), jnp.bfloat16)
                for n in (nh, nkv, nkv)]
        # the suite asks for float32-exact products; the chip's kernels
        # take their bf16 operands as they are
        with jax.default_matmul_precision("default"):
            text = re.sub(
                r" at 0x[0-9a-f]+", "",
                str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(*args)))
        assert text.count("pallas_call") == 2
        digests[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digests == {"t8192_h32on8_d64": "e4c8921e21abf131",
                       "t8192_h32on2_d128": "fd0330c7a56451b1",
                       "t4096_h16_d128": "3d38a98b1ea26c08"}


# ---------------------------------------------------------------------------
# a sliding window beside `causal` (ISSUE 41): query i sees keys
# i - window < j <= i; the blocked kernels visit the band's tiles only
# ---------------------------------------------------------------------------

def _plain_window(q, k, v, nh, nkv, window):
    """`_plain_packed` under a literal [T, T] mask of the band."""
    import jax
    import jax.numpy as jnp
    b, t, _ = q.shape
    d = q.shape[2] // nh
    heads = lambda x, n: jnp.repeat(
        x.reshape(b, t, n, -1).transpose(0, 2, 1, 3), nh // n, axis=1)
    pos = np.arange(t)
    band = (pos[None, :] <= pos[:, None]) & (pos[:, None] - pos[None, :]
                                             < window)
    s = jnp.einsum("bhqd,bhkd->bhqk", heads(q, nh), heads(k, nkv))
    s = jnp.where(band, s / np.sqrt(d), -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), heads(v, nkv))
    return out.transpose(0, 2, 1, 3).reshape(b, t, -1)


# (T, query heads, key/value heads, d, window). On the interpreted path T
# 1,536 is three blocks of 512 a side (a window of one block, of a block
# and a half, of less than a block), T 256 and 512 with one key/value head a
# query head the one-pass kernels; groups of 6 and of 8 as the two layer
# kinds of Laguna have them
WINDOW_CASES = [(1536, 6, 1, 64, 512), (1536, 8, 1, 64, 768),
                (1536, 2, 2, 128, 100), (256, 2, 2, 64, 96),
                (512, 8, 1, 64, 200)]


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["blockwise_jax", "pallas_interpreted"])
@pytest.mark.parametrize("t,nh,nkv,d,window", WINDOW_CASES)
def test_a_window_against_a_dense_masked_softmax(monkeypatch, interpret, t,
                                                 nh, nkv, d, window):
    """Forward and all three gradients, on the path the CPU tests run and on
    the kernels through the interpreter."""
    import jax
    import jax.numpy as jnp
    fa = _fa_mod()
    monkeypatch.setattr(fa, "FORCE_PALLAS_INTERPRET", interpret)
    q, k, v, w = _packed_inputs(1, t, nh, nkv, d, d)

    def run(f):
        return jax.value_and_grad(lambda *x: jnp.sum(f(*x) * w), (0, 1, 2))(
            q, k, v)

    got = run(lambda q, k, v: fa.flash_attention_packed(
        q, k, v, nh, causal=True, num_kv_heads=nkv, window=window))
    want = run(lambda q, k, v: _plain_window(q, k, v, nh, nkv, window))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("window", [512, 384, 100, 1])
@pytest.mark.parametrize("kv_group", [1, 2])
def test_windowed_blocked_kernels_match_blockwise_jax(kv_group, window):
    """4 x 4 blocks of 128 through the interpreter: a window of four blocks
    (the whole sequence: nothing dropped), of three, narrower than a block,
    and of the query's own key alone; out, lse, dq, dk, dv of the scheduled
    kernels against the blockwise-JAX path under the same mask."""
    fa = _fa_mod()
    q, k, v, g, _ = _blocked_inputs(64, kv_group, "none")
    scale = 1.0 / np.sqrt(64)
    out, lse = fa._flash_fwd_pallas(q, k, v, None, scale, True, 128, 128,
                                    interpret=True, kv_group=kv_group,
                                    window=window)
    got = (out, lse) + fa._flash_bwd_pallas(
        q, k, v, None, g, lse, out, scale, True, 128, 128, interpret=True,
        kv_group=kv_group, window=window)[:3]
    kr, vr = fa._repeat_kv(k, kv_group), fa._repeat_kv(v, kv_group)
    out, lse = fa._flash_fwd_jax(q, kr, vr, None, scale, True, 128,
                                 window=window)
    want = (out, lse) + fa._flash_bwd_jax(
        (q, kr, vr, None, None, out, lse), g, sm_scale=scale, causal=True,
        block_k=128, dropout_rate=0.0, has_bias=False, window=window)[:3]
    for name, a, b, tol in zip(("out", "lse", "dq", "dk", "dv"), got, want,
                               (2e-5, 1e-5, 2e-4, 2e-4, 2e-4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                                   atol=tol, err_msg=name)
    if window == 1:     # a query sees itself: out is v, the row's own
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(kr * 0 + vr),
                                   rtol=1e-6, atol=1e-6)


def test_a_window_with_a_per_q_bias_keeps_the_dq_kernel_and_zeroes_behind():
    """The two-kernel backward under a window: the dq kernel walks the whole
    square for the per-q bias gradient and zeroes the tiles behind the band
    as it does those above the diagonal."""
    fa = _fa_mod()
    q, k, v, g, bias = _blocked_inputs(64, 1, "per_q")
    scale = 1.0 / np.sqrt(64)
    out, lse = fa._flash_fwd_pallas(q, k, v, bias, scale, True, 128, 128,
                                    interpret=True, window=130)
    dq, dk, dv, dbias = fa._flash_bwd_pallas(
        q, k, v, bias, g, lse, out, scale, True, 128, 128, interpret=True,
        window=130)
    out2, lse2 = fa._flash_fwd_jax(q, k, v, bias, scale, True, 128,
                                   window=130)
    want = fa._flash_bwd_jax(
        (q, k, v, bias, None, out2, lse2), g, sm_scale=scale, causal=True,
        block_k=128, dropout_rate=0.0, has_bias=True, window=130)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), (dq, dk, dv, dbias),
                          want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)
    pos = np.arange(512)
    band = (pos[None, :] <= pos[:, None]) & (pos[:, None] - pos[None, :]
                                             < 130)
    assert not np.asarray(dbias)[:, ~band].any()


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["blockwise_jax", "pallas_interpreted"])
def test_a_window_as_long_as_the_sequence_is_the_causal_call(monkeypatch,
                                                             interpret):
    """`window >= T` is `causal` bit for bit, forward and gradients, packed
    and 4D: the call is the causal one."""
    import jax
    import jax.numpy as jnp
    fa = _fa_mod()
    monkeypatch.setattr(fa, "FORCE_PALLAS_INTERPRET", interpret)
    q, k, v, w = _packed_inputs(1, 1024, 4, 2, 64, 64)

    def run(**kw):
        return jax.value_and_grad(lambda *x: jnp.sum(
            fa.flash_attention_packed(*x, 4, causal=True, num_kv_heads=2,
                                      **kw) * w), (0, 1, 2))(q, k, v)

    want = run()
    for window in (1024, 5000):
        got = run(window=window)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    narrower = run(window=1023)
    assert not np.array_equal(np.asarray(narrower[0]), np.asarray(want[0]))
    split = lambda x, n: x.reshape(1, 1024, n, -1).transpose(0, 2, 1, 3)
    q4, k4, v4 = split(q, 4), split(k, 2), split(v, 2)
    assert np.array_equal(
        np.asarray(fa.flash_attention(q4, k4, v4, causal=True, window=1024)),
        np.asarray(fa.flash_attention(q4, k4, v4, causal=True)))
    np.testing.assert_allclose(
        fa.flash_attention(q4, k4, v4, causal=True, window=77).transpose(
            0, 2, 1, 3).reshape(1, 1024, 256),
        _plain_window(q, k, v, 4, 2, 77), rtol=2e-4, atol=2e-4)


def test_the_band_s_tile_list_by_hand():
    """T 8,192 under a window of 512: at blocks of 1,024 a q block sees its
    own k block and the one before (15 tiles), at 512 the same (31), at 256
    its own and the two before (93); q-block-major and k-block-major list
    the same tiles; the scores the window needs are 4,063,488 a head."""
    fa = _fa_mod()
    t, w = 8192, 512
    for block, tiles in ((1024, 15), (512, 31), (256, 93)):
        n = t // block
        qi, ki = fa._tile_schedule(n, n, block, block, True, window=w)
        assert len(qi) == tiles
        by_hand = [(i, j) for i in range(n) for j in range(n)
                   if j * block <= i * block + block - 1
                   and i * block - (j * block + block - 1) < w]
        assert list(zip(qi.tolist(), ki.tolist())) == by_hand
        qk, kk = fa._tile_schedule(n, n, block, block, True, k_major=True,
                                   window=w)
        assert sorted(zip(qk.tolist(), kk.tolist())) == by_hand
        assert (np.diff(kk) >= 0).all()
    assert fa._scores_visible(t, True, w) == 4_063_488
    assert fa._scores_visible(t, True) == 33_558_528
    assert fa._scores_visible(t, False) == t * t
    assert fa._scores_visible(256, True, 512) == 256 * 257 // 2
    # a q block's oldest query reaches `window - 1` keys back: one key more
    # than a block and a second block behind its own is visited
    assert len(fa._tile_schedule(4, 4, 128, 128, True, window=129)[0]) == 7
    assert len(fa._tile_schedule(4, 4, 128, 128, True, window=130)[0]) == 9
    # the blocks a windowed call of the cell's length takes
    assert fa._pick_dense_blocks(8192, 512) == (fa._WINDOW_BLOCK,) * 2
    assert fa._pick_dense_blocks(8192) == (1024, 1024)
    assert fa._pick_dense_blocks(8192, 2048) == (1024, 1024)
    assert fa._pick_dense_blocks(1536, 512) == (512, 512)


def test_the_tile_gauges_say_the_call_s_kind_and_the_scores_it_needs():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import get_registry
    fa = _fa_mod()
    _clear_tile_gauges()
    for n in ("scores_scheduled", "scores_visible"):
        get_registry().remove_matching("flash_attention/" + n)
    x = jax.ShapeDtypeStruct((2, 2048, 64), jnp.float32)
    lse = jax.ShapeDtypeStruct((2, 2048), jnp.float32)
    for kw in ({}, {"window": 300}):
        jax.eval_shape(lambda q: fa._flash_fwd_pallas(
            q, q, q, None, 0.125, True, 256, 256, interpret=True, **kw), x)
        jax.eval_shape(lambda q, l: fa._flash_bwd_pallas(
            q, q, q, None, q, l, q, 0.125, True, 256, 256, interpret=True,
            **kw), x, lse)
    got = {(s["labels"]["call"], s["labels"]["kernel"],
            s["name"].split("/")[1]): s["value"]
           for s in get_registry().series()
           if s["name"].startswith("flash_attention/")}
    # 8 x 8 blocks of 256: the triangle is 36 tiles, the band of 300 keys a
    # q block's own k block and the two before it (8 + 7 + 6)
    for kernel in ("fwd", "bwd"):
        assert got["causal", kernel, "tiles_scheduled"] == 36
        assert got["window", kernel, "tiles_scheduled"] == 21
        assert got["window", kernel, "tiles_grid"] == 64
        assert got["window", kernel, "tiles_masked"] == 21
        assert got["window", kernel, "scores_scheduled"] == 21 * 256 * 256
        assert got["window", kernel, "scores_visible"] == (
            300 * 301 // 2 + (2048 - 300) * 300)
        assert got["causal", kernel, "scores_visible"] == 2048 * 2049 // 2


def test_window_refusals_say_what_holds():
    import jax
    import jax.numpy as jnp
    import importlib
    ring_attention = importlib.import_module(
        "paddle_tpu.parallel.ring_attention")
    fa = _fa_mod()
    q, k, v, _ = _packed_inputs(1, 64, 4, 2, 16, 16)
    with pytest.raises(ValueError, match="needs causal=True"):
        fa.flash_attention_packed(q, k, v, 4, num_kv_heads=2, window=8)
    for bad in (0, -3, 2.5, True):
        with pytest.raises(ValueError, match="whole number of keys >= 1"):
            fa.flash_attention_packed(q, k, v, 4, causal=True,
                                      num_kv_heads=2, window=bad)
    seg = jnp.ones((1, 64), jnp.int32)
    with pytest.raises(ValueError, match="holds no window"):
        fa.flash_attention_packed_sparse(q, q, q, 4, seg, seg, causal=True,
                                         window=8)
    with pytest.raises(ValueError, match="no sliding window under the ring"):
        ring_attention.ring_self_attention(
            jnp.zeros((1, 2, 8, 16)), jnp.zeros((1, 2, 8, 16)),
            jnp.zeros((1, 2, 8, 16)), mesh=None, window=4)


def test_the_layer_writes_the_window_only_where_there_is_one():
    """`layers.flash_attention(..., window=)`: the attribute is absent where
    None (an op without it is as it was), and the op runs the band."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = layers.data("q", [64, 6 * 16], dtype="float32")
        k = layers.data("k", [64, 16], dtype="float32")
        v = layers.data("v", [64, 16], dtype="float32")
        plain = layers.flash_attention(q, k, v, causal=True, num_heads=6,
                                       num_kv_heads=1)
        banded = layers.flash_attention(q, k, v, causal=True, num_heads=6,
                                        num_kv_heads=1, window=9)
    a, b = [op.attrs for op in main.global_block().ops
            if op.type == "flash_attention"]
    assert "window" not in a and b["window"] == 9
    qv, kv, vv, _ = _packed_inputs(2, 64, 6, 1, 16, 16)
    exe = fluid.Executor(fluid.TPUPlace())
    got_plain, got = exe.run(main, feed={
        "q": np.asarray(qv), "k": np.asarray(kv), "v": np.asarray(vv)},
        fetch_list=[plain, banded])
    np.testing.assert_allclose(got, _plain_window(qv, kv, vv, 6, 1, 9),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_plain,
                               _plain_packed(qv, kv, vv, 6, 1, True),
                               rtol=2e-5, atol=2e-5)


def test_without_a_window_the_four_cells_calls_are_the_parent_s():
    """`window=None` traces to the jaxprs of the parent commit (2efe038, read
    from a copy of it) to the character: LFM2's, Nemotron's and Ouro's calls
    as `test_equal_head_sizes_trace_to_the_parent_s_kernels` holds them, and
    JoyAI's 192-wide keys on 128-wide values."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    fa = _fa_mod()
    fa_on_tpu = fa._on_tpu
    fa._on_tpu = lambda: True
    try:
        def loss(q, k, v):
            return jnp.sum(fa.flash_attention_packed(
                q, k, v, 32, causal=True).astype(jnp.float32))
        args = [jax.ShapeDtypeStruct((2, 8192, 32 * w), jnp.bfloat16)
                for w in (192, 192, 128)]
        with jax.default_matmul_precision("default"):
            text = re.sub(
                r" at 0x[0-9a-f]+", "",
                str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(*args)))
    finally:
        fa._on_tpu = fa_on_tpu
    assert text.count("pallas_call") == 2
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        "cdd37b7182978701")
