"""The chunked gated delta rule (ops/linear_attn_ops.py) against the
token-by-token recurrence the benchmark's plain reference keeps
(benchmark/configs/kimi_linear_48b_a3b_reference.py, which imports nothing of
the program): forward and all five gradients in float32, under strong decay
and almost none, with beta at 0 and at 1, under bf16 operands, through the
registered op, and what its backward rule keeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import kimi_linear_48b_a3b_reference as ref
from paddle_tpu.ops import linear_attn_ops as la
from paddle_tpu.ops.eager import call as eager

B, T, H, K, V, CHUNK = 2, 192, 2, 16, 24, 64     # three chunks, K != V


def _inputs(seed, decay, beta=None, dtype=jnp.float32):
    """q, k unit vectors a head; v normal; g = -decay x a draw on
    (0.5, 1.5) a channel; beta a sigmoid's draw, or the constant given."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, K)))
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, V))
    g = -decay * jax.random.uniform(ks[3], (B, T, H, K), minval=0.5,
                                    maxval=1.5)
    b = (jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
         if beta is None else jnp.full((B, T, H), beta))
    w = jax.random.normal(ks[5], (B, T, H, V))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, b), w


def _recurrence(q, k, v, g, beta):
    scale = q.shape[-1] ** -0.5
    f32 = lambda x: x.astype(jnp.float32)
    return jax.vmap(lambda *a: ref.delta_rule(*a, scale))(
        f32(q), f32(k), f32(v), g, beta)


def _grads(fn, args, w):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                    argnums=tuple(range(len(args))))(*args)


def _close(got, want, tol, what):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / scale
    assert err <= tol, f"{what}: {err:.2e} of the largest entry, over {tol}"


# decay a step: 4 (a chunk's cumulative log-decay reaches -256 and beyond,
# past float32's e^-88: exp(-G) would be infinite), 0.1 (the fresh draw's
# order), 1e-3 (almost none: the state lives through all three chunks)
@pytest.mark.parametrize("decay", [4.0, 0.1, 1e-3])
@pytest.mark.parametrize("beta", [None, 0.0, 1.0])
def test_the_rule_is_the_recurrence_forward_and_backward(decay, beta):
    """float32 against float32, 1e-5 of the largest entry: both sides are
    the same sums in another order (the substitution, the chunk products),
    so float32 rounding is all that separates them; the largest read here is
    7.6e-6 (dg at a decay of 4 a step), every other under 1e-6."""
    args, w = _inputs(0, decay, beta)
    rule = lambda *a: la.gated_delta_rule(*a, CHUNK, None)
    _close(rule(*args), _recurrence(*args), 1e-5, "o")
    got, want = _grads(rule, args, w), _grads(_recurrence, args, w)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        _close(a, b, 1e-5, name)


def test_chunks_taken_a_group_at_a_time_give_the_same_numbers(monkeypatch):
    """At the cell's size the chunk-local terms are made 2,048
    chunks-times-heads at a time; here 4 at a time (three groups of one
    chunk) against all twelve at once."""
    args, w = _inputs(6, 0.1)
    rule = lambda *a: la.gated_delta_rule(*a, CHUNK, None)
    whole = (rule(*args),) + _grads(rule, args, w)
    monkeypatch.setattr(la, "ROWS", 4)
    cut = (rule(*args),) + _grads(rule, args, w)
    for a, b in zip(cut, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_beta_zero_writes_nothing_and_beta_one_replaces_the_value():
    """With beta 0 the state stays 0 and so does o; with beta 1, no decay
    and one key repeated, the state recalls exactly the last value."""
    args, _ = _inputs(1, 0.1, beta=0.0)
    assert float(jnp.max(jnp.abs(la.gated_delta_rule(*args, CHUNK,
                                                     None)))) == 0.0
    (q, k, v, g, b), _ = _inputs(2, 0.0, beta=1.0)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    o = la.gated_delta_rule(k, k, v, g, b, CHUNK, 1.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(v), atol=2e-5)


def test_bf16_operands_stay_close_to_float32():
    """bf16 q, k, v (decays, beta, the substitution and the states float32)
    against the float32 op on the same rounded inputs: the products' operands
    are rounded to 8 bits of mantissa (2^-9 relative a factor) and summed in
    float32 over 16 to 64 terms, and the state carries the rounding on. Read
    here: 6.2e-3 of the largest entry on o, 2.4e-3 to 6.0e-3 on the five
    gradients; 9e-3 leaves a factor of 1.45. The same inputs rounded to int8
    (absmax a tensor) and run in float32 read 1.8e-2 on o and 1.1e-2 to
    1.4e-2 on the gradients: every one of the six fails 9e-3."""
    args, w = _inputs(3, 0.1, dtype=jnp.bfloat16)
    rule = lambda *a: la.gated_delta_rule(*a, CHUNK, None)
    up = tuple(x.astype(jnp.float32) for x in args)
    got = rule(*args)
    assert got.dtype == jnp.bfloat16
    _close(got, rule(*up), 9e-3, "o")
    g_got, g_want = _grads(rule, args, w), _grads(rule, up, w)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), g_got, g_want):
        assert a.dtype == (jnp.bfloat16 if name in ("dq", "dk", "dv")
                           else jnp.float32), name
        _close(a, b, 9e-3, name)


def test_the_registered_op_reports_the_decay_floor_and_counts_its_form():
    from paddle_tpu.observability import get_registry
    from paddle_tpu.ops import eager as eager_mod

    eager_mod._jit_cache.clear()
    count = lambda: sum(
        s["value"] for s in get_registry().series()
        if s["name"] == "ops/kda_lowered"
        and s["labels"].get("path") == "einsum")
    before = count()
    (q, k, v, g, b), _ = _inputs(4, 0.1)
    out = eager("gated_delta_rule", {"Q": [q], "K": [k], "V": [v], "G": [g],
                                     "Beta": [b]}, {"chunk": CHUNK})
    np.testing.assert_allclose(
        np.asarray(out["Out"][0]),
        np.asarray(la.gated_delta_rule(q, k, v, g, b, CHUNK, None)),
        rtol=1e-6, atol=1e-7)
    chunks = np.asarray(g).reshape(B, T // CHUNK, CHUNK, H, K).sum(2)
    np.testing.assert_allclose(float(out["DecayFloor"][0]), chunks.min(),
                               rtol=1e-6)
    assert count() == before + 1
    with pytest.raises(ValueError, match="whole chunks"):
        eager("gated_delta_rule", {"Q": [q], "K": [k], "V": [v], "G": [g],
                                   "Beta": [b]}, {"chunk": 128})


def test_the_backward_rule_keeps_inputs_and_entering_states_alone():
    """The residuals of the forward rule are the five inputs (as the loops
    read them: heads before positions) and the state entering each chunk,
    [chunks, B, H, K, V] float32: no chunk-local term (a [chunk, chunk]
    matrix, W, U) is carried to the backward pass."""
    args, _ = _inputs(5, 0.1)
    (out, _), (ins, gate, states) = la._rule_fwd(*args, None, CHUNK,
                                                 K ** -0.5, 0.0)
    assert out.shape == (B, T, H, V) and gate is None and len(ins) == 5
    for kept, arg in zip(ins[:4], args):
        np.testing.assert_array_equal(np.asarray(kept),
                                      np.asarray(arg).swapaxes(1, 2))
    assert ins[4].shape == (B, H, T, 1)
    assert states.shape == (T // CHUNK, B, H, K, V)
    assert states.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(states[0]))) == 0.0      # S_0 = 0
