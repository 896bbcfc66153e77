"""The gated delta rule's Pallas kernels (ops/pallas_kernels/kda_chunk.py)
through the Pallas interpreter on the CPU, against the einsum form of
ops/linear_attn_ops.py (their oracle) and against the token-by-token
recurrence of the benchmark's plain reference: the result, the decay floor
and the gradients of q, k, v, the gate's values, beta, A_log and dt_bias; and
the rule that picks a form."""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import kimi_linear_48b_a3b_reference as ref
from paddle_tpu.ops import linear_attn_ops as la
from paddle_tpu.ops.eager import call as eager
from paddle_tpu.ops.pallas_kernels import kda_chunk as kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, T, H, D, CHUNK = 1, 256, 2, 128, 64       # two tiles of two chunks
SCALE, EPS = D ** -0.5, 1e-6


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(kernels, "FORCE_PALLAS_INTERPRET", True)


def _inputs(seed, decay, gated, l2, dtype=jnp.float32, beta=None):
    """q, k normal draws (unit vectors where the op does not normalise
    them); v normal; beta a sigmoid's draw or the constant given; the
    log-decay -decay x a draw on (0.5, 1.5) a channel, or with `gated` raw
    gate values in the operands' dtype with A = decay x a draw on (0.5, 1.5)
    a head and a bias a channel (softplus of them is about 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    shape = (B, T, H, D)
    q, k, v, w = (jax.random.normal(ks[i], shape) for i in range(4))
    if not l2:
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        q, k = unit(q), unit(k)
    draw = jax.random.uniform(ks[4], shape, minval=0.5, maxval=1.5)
    if gated:
        g = (draw - 0.5).astype(dtype)
        gate = (jnp.log(decay * jax.random.uniform(ks[5], (H,), minval=0.5,
                                                   maxval=1.5)),
                0.5 * jax.random.normal(ks[6], (H, D)))
    else:
        g, gate = -decay * draw, None
    b = (jax.nn.sigmoid(jax.random.normal(ks[7], (B, T, H)))
         if beta is None else jnp.full((B, T, H), beta))
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    return (q, k, v, g, b, gate), w, (EPS if l2 else 0.0)


def _recurrence(q, k, v, g, beta, gate, l2_eps):
    """The reference's recurrence on what the op is handed: the same
    normalisation (rounded to the operands' dtype, as the op's) and gate,
    then position by position in float32."""
    f32 = lambda x: x.astype(jnp.float32)
    if l2_eps:
        unit = lambda x: (f32(x) / jnp.maximum(jnp.linalg.norm(
            f32(x), axis=-1, keepdims=True), l2_eps)).astype(x.dtype)
        q, k = unit(q), unit(k)
    g = f32(g)
    if gate is not None:
        g = -jnp.exp(gate[0])[:, None] * jax.nn.softplus(g + gate[1])
    return jax.vmap(lambda *a: ref.delta_rule(*a, SCALE))(
        f32(q), f32(k), f32(v), g, beta)


@functools.cache
def _programs(gated, l2, scale=SCALE):
    """For one way of calling the rule, three jitted programs (w, *args) ->
    [o, the gradients of sum(o w) in the leaves of `args`..., the floor]: by
    the kernels, by the einsum form, by the recurrence (its floor None).
    Cached: the cases that differ in values alone share the compilations."""
    l2_eps = EPS if l2 else 0.0
    n = 6 if gated else 5               # without a gate `args` ends in None

    def program(fn):
        def loss(w, *a):
            out, floor = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * w), (out, floor)

        def run(w, *a):
            grads, (out, floor) = jax.grad(
                loss, argnums=tuple(range(1, n + 1)), has_aux=True)(w, *a)
            return [out] + jax.tree_util.tree_leaves(grads) + [floor]
        return jax.jit(run)

    return (program(lambda *a: kernels.kda_rule(*a, scale, l2_eps)),
            program(lambda *a: la._kda_rule(*a, CHUNK, scale, l2_eps)),
            program(lambda *a: (_recurrence(*a, l2_eps), None)))


def _close(got, want, tol, what):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-30)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32)))) / scale
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), what
    assert err <= tol, f"{what}: {err:.2e} of the largest entry, over {tol}"


NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta", "dA_log", "ddt_bias")


# decay 4 a step: a chunk's cumulative log-decay reaches -256 and beyond,
# past float32's e^-88 (the floor is asserted); 0.05: the fresh draw's order,
# the state lives through all four chunks. Four ways of calling the rule, in
# which gate inside / log-decay given, l2 on / off and float32 / bf16 each
# appear twice, at both decays.
@pytest.mark.parametrize("decay", [4.0, 0.05])
@pytest.mark.parametrize("gated,l2,dtype", [
    (True, True, "bfloat16"), (False, False, "float32"),
    (True, False, "float32"), (False, True, "bfloat16")])
def test_the_kernels_are_the_einsum_form_and_the_recurrence(
        interpreter, gated, l2, dtype, decay):
    """float32 operands: all three are the same sums in another order.
    Against the recurrence 5e-5 of the largest entry (read here: up to
    1.3e-5, on A_log's gradient at a decay of 4 a step, where the einsum
    form itself stands 1.2e-4 from the recurrence: A_log's and dt_bias's
    gradients are sums over every position, and against the einsum form they
    get 3e-4, everything else 5e-5, read up to 6.1e-6). bf16 operands:
    against the einsum form on the same rounded inputs 1.5e-2 (both round
    their decayed operands to 8 bits, at different reference positions;
    read: up to 8.7e-3), against the float32 recurrence 2e-2 (`chip_smoke.py
    kda`'s tolerance at the cell's shape)."""
    args, w, _ = _inputs(0, decay, gated, l2, jnp.dtype(dtype))
    *got, floor = _programs(gated, l2)[0](w, *args)
    wants = [program(w, *args) for program in _programs(gated, l2)[1:]]
    np.testing.assert_allclose(float(floor), float(wants[0][-1]), rtol=1e-5)
    if decay == 4.0:
        assert float(floor) < -88.0
    assert got[0].dtype == jnp.dtype(dtype) and got[3].dtype == got[0].dtype
    tol_form, tol_steps = ((5e-5, 5e-5) if dtype == "float32"
                           else (1.5e-2, 2e-2))
    for name, a, b, c in zip(NAMES, got, *wants):
        summed = dtype == "float32" and name in ("dA_log", "ddt_bias")
        _close(a, b, 3e-4 if summed else tol_form,
               f"{name} against the einsum form")
        _close(a, c, tol_steps, f"{name} against the recurrence")


@pytest.mark.parametrize("beta", [0.0, 1.0])
def test_beta_zero_writes_nothing_and_beta_one_replaces_the_value(
        interpreter, beta):
    """With beta 0 the state stays 0 and so does o; with beta 1, no decay
    and one key repeated, the state recalls exactly the last value. And the
    gradients at both ends of beta's range are the einsum form's."""
    args, w, _ = _inputs(1, 0.05, False, False, beta=beta)
    by_kernels, by_einsums, _ = _programs(False, False)
    got = by_kernels(w, *args)
    if beta == 0.0:
        assert float(jnp.max(jnp.abs(got[0]))) == 0.0
    else:
        q, k, v, g, b, gate = args
        k = jnp.broadcast_to(k[:, :1], k.shape)
        o = _programs(False, False, 1.0)[0](w, k, k, v, 0.0 * g, b, gate)[0]
        np.testing.assert_allclose(np.asarray(o), np.asarray(v), atol=2e-5)
    for name, a, b in zip(NAMES, got, by_einsums(w, *args)):
        _close(a, b, 5e-5, name)


def test_the_backward_kernel_keeps_inputs_and_a_state_a_tile(interpreter):
    """The residuals are the op's inputs where they lie and the state
    entering each tile of two chunks, transposed: [B, H, T / 128, V, K]
    float32, half of what the einsum form keeps."""
    args, _, l2_eps = _inputs(2, 0.05, True, True)
    (out, _), res = kernels._rule_fwd(*args, SCALE, l2_eps)
    states = res[-1]
    assert out.shape == (B, T, H, D)
    assert states.shape == (B, H, T // kernels.TILE, D, D)
    assert states.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(states[:, :, 0]))) == 0.0       # S_0 = 0
    _, _, _, chunk_states = la._rule_states(*args, CHUNK, SCALE, l2_eps)
    np.testing.assert_allclose(
        np.asarray(states[0, :, 1]),
        np.asarray(chunk_states[2, 0]).swapaxes(-1, -2), rtol=2e-5, atol=1e-6)


def _count(path):
    from paddle_tpu.observability import get_registry
    return sum(s["value"] for s in get_registry().series()
               if s["name"] == "ops/kda_lowered"
               and s["labels"].get("path") == path)


def test_shapes_and_backend_pick_the_form(monkeypatch):
    """"pallas" for the cell's shape on a TPU backend; "einsum" on the CPU,
    for another head size, chunk or dtype, for T that is no whole tile, and
    under a mesh; the registered op counts the form it took."""
    cell = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16)
    assert la.rule_path(cell, cell, 64) == "einsum"              # the CPU
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    assert la.rule_path(cell, cell, 64) == "pallas"
    assert la.rule_path(cell, cell, 64, under_mesh=True) == "einsum"
    assert la.rule_path(cell, cell, 32) == "einsum"
    like = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    assert la.rule_path(like((2, 8192, 32, 64)), cell, 64) == "einsum"
    assert la.rule_path(cell, like((2, 8192, 32, 64)), 64) == "einsum"
    assert la.rule_path(like((2, 8192 + 64, 32, 128)), cell, 64) == "einsum"
    assert la.rule_path(like(cell.shape, jnp.float16), cell, 64) == "einsum"
    assert la.rule_path(like(cell.shape, jnp.float32), cell, 64) == "pallas"
    assert la.rule_path(like((1, 128, 3, 128)), like((1, 128, 3, 128)),
                        64) == "pallas"                  # an odd head count


def test_the_registered_op_takes_and_counts_the_kernels(interpreter):
    from paddle_tpu.ops import eager as eager_mod

    (q, k, v, g, b, (a_log, dt_bias)), _, _ = _inputs(3, 0.05, True, True)
    feed = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [b],
            "ALog": [a_log], "DtBias": [dt_bias.reshape(-1)]}
    attrs = {"chunk": CHUNK, "qk_l2norm": EPS}
    eager_mod._jit_cache.clear()
    before = _count("pallas"), _count("einsum")
    out = eager("gated_delta_rule", feed, attrs)
    assert (_count("pallas"), _count("einsum")) == (before[0] + 1, before[1])
    want, floor = la._kda_rule(q, k, v, g, b, (a_log, dt_bias), CHUNK, SCALE,
                               EPS)
    _close(out["Out"][0], want, 5e-5, "Out")
    np.testing.assert_allclose(float(out["DecayFloor"][0]), float(floor),
                               rtol=1e-5)
    # a head of 64 is the einsum form's, interpreter or not
    half = {n: [x[..., :64]] if n in "QKVG" else [x] for n, (x,) in
            feed.items() if n in ("Q", "K", "V", "G", "Beta")}
    eager_mod._jit_cache.clear()
    eager("gated_delta_rule", half, {"chunk": CHUNK})
    assert (_count("pallas"), _count("einsum")) == (before[0] + 1,
                                                    before[1] + 1)
    eager_mod._jit_cache.clear()


_IMPORT = """
import sys
sys.path.insert(0, {repo!r})
import paddle_tpu
from paddle_tpu.ops import linear_attn_ops
print("PALLAS", sorted(m for m in sys.modules if m.startswith(
    ("jax.experimental.pallas", "paddle_tpu.ops.pallas_kernels.kda"))))
"""


def test_import_paddle_tpu_does_not_import_the_kernels():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _IMPORT.format(repo=REPO)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "PALLAS []"
