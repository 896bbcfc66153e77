"""The grouped product's Pallas kernels (ops/pallas_kernels/grouped_ffn.py)
against the loops over tiles of parallel/moe.py, through the interpreter:
output, dx, the pair weights' gradient and every weight and bias gradient,
by the expert's form (plain or gated, biases given or None), by what the
routing makes of the walk, and by the hidden width."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import get_registry
from paddle_tpu.ops.common import relu2
from paddle_tpu.ops.pallas_kernels import grouped_ffn as kernels
from paddle_tpu.parallel import moe

N, D, TILE, HELD = 40, 16, 8, 4


def _even(rng, n):
    """Every token's two experts among the four held and four absent."""
    return np.stack([rng.permutation(8)[:2] for _ in range(n)])


def _expert_1_unused(rng, n):
    return np.stack([rng.permutation([0, 2, 3, 5, 6, 7])[:2]
                     for _ in range(n)])


def _all_on_expert_1(rng, n):
    """Five tiles on one held expert, none on the three others."""
    return np.stack([np.full(n, 1), rng.randint(4, 8, n)], axis=1)


def _one_row_in_the_last_tile(rng, n):
    """Expert 2 holds two tiles and one row; expert 0 one pair."""
    idx = np.stack([np.full(n, 5), np.full(n, 6)], axis=1)
    idx[:2 * TILE + 1, 0] = 2
    idx[n - 1, 1] = 0
    return idx


def _no_pair_held(rng, n):
    return np.stack([np.full(n, 6), np.full(n, 7)], axis=1)


FORMS = {
    # act, gated, biased
    "plain": (relu2, False, False),
    "plain_biased": (jax.nn.gelu, False, True),
    "gated": (jax.nn.silu, True, False),
    "gated_biased": (jax.nn.silu, True, True),
}
LOADS = {
    # the routing, and the held experts it leaves without a pair
    "even": (_even, ()),
    "every_pair_on_one_expert": (_all_on_expert_1, (0, 2, 3)),
    "an_expert_without_a_pair": (_expert_1_unused, (1,)),
    "one_row_in_a_last_tile": (_one_row_in_the_last_tile, (1, 3)),
    "no_pair_held": (_no_pair_held, (0, 1, 2, 3)),
}
WIDTHS = {
    # the hidden width: not whole lanes (W1 and W3 held turned, as
    # Nemotron's 1,856 is), whole lanes in several blocks, in one
    "h200_gated_biased": ("gated_biased", 200),
    "h200_plain": ("plain", 200),
    "h256_gated_biased": ("gated_biased", 256),
    "h128_plain": ("plain", 128),
}


def _inputs(form, routing, h, dtype, seed=0):
    act, gated, biased = FORMS[form]
    rng = np.random.RandomState(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    x = draw(N, D).astype(dtype)
    idx = jnp.asarray(routing(rng, N), jnp.int32)
    weight = jnp.asarray(rng.rand(N, 2) + 0.1, jnp.float32)
    params = {
        "w1": draw(HELD, D, h, scale=D ** -0.5),
        "b1": draw(HELD, h, scale=0.1) if biased else None,
        "w2": draw(HELD, h, D, scale=h ** -0.5),
        "b2": draw(HELD, D, scale=0.1) if biased else None,
        "w3": draw(HELD, D, h, scale=D ** -0.5) if gated else None,
    }
    return act, x, idx, weight, params, draw(N, D)


def _run(form, routing, h, dtype, interpret, monkeypatch):
    """(y, tokens, pairs) and the gradients of Σ y ∘ ct by x, the pair
    weights and every parameter the form has."""
    monkeypatch.setattr(kernels, "FORCE_PALLAS_INTERPRET", interpret)
    act, x, idx, weight, params, ct = _inputs(form, routing, h, dtype)
    names = [k for k, v in params.items() if v is not None]

    def loss(x, weight, *given):
        p = dict(params, **dict(zip(names, given)))
        y, tokens, pairs = moe.experts_ffn(
            x, moe.Routing(idx, weight, jnp.zeros(())), p["w1"], p["b1"],
            p["w2"], p["b2"], 0, act, tile=TILE, w3=p["w3"])
        return jnp.sum(y.astype(jnp.float32) * ct), (y, tokens, pairs)

    given = [params[k] for k in names]
    (_, out), grads = jax.value_and_grad(
        loss, tuple(range(2 + len(given))), has_aux=True)(x, weight, *given)
    return out, dict(zip(["x", "weight"] + names, grads))


def _close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


def _kernels_against_loops(form, routing, h, dtype, tol, empty, monkeypatch):
    (y0, tokens0, pairs0), g0 = _run(form, routing, h, dtype, False,
                                     monkeypatch)
    (y1, tokens1, pairs1), g1 = _run(form, routing, h, dtype, True,
                                     monkeypatch)
    assert y1.dtype == y0.dtype == jnp.dtype(dtype)
    _close(y1, y0, tol)
    assert np.array_equal(tokens0, tokens1) and int(pairs0) == int(pairs1)
    # dropless: every held pair is a row of some tile
    assert int(pairs1) == int(np.asarray(tokens1).sum())
    assert not np.any(np.asarray(tokens1)[list(empty)])
    assert set(g0) == set(g1)
    for name in g0:
        assert g1[name].dtype == g0[name].dtype, name
        _close(g1[name], g0[name], tol)
        if name not in ("x", "weight"):
            # an expert no tile names is written all the same: zeros
            g = np.asarray(g1[name])
            held = [e for e in range(HELD) if e not in empty]
            assert not np.any(g[list(empty)])
            assert all(np.any(g[e]) for e in held)
    if len(empty) == HELD:
        assert not np.any(np.asarray(y1, np.float32))
        assert not np.any(np.asarray(g1["x"], np.float32))
        assert not np.any(np.asarray(g1["weight"]))


@pytest.mark.parametrize("load", list(LOADS))
@pytest.mark.parametrize("form", list(FORMS))
def test_kernels_match_the_loops(form, load, monkeypatch):
    """Output and every gradient (dx, the pair weights', dW1, dW2, dW3, db1,
    db2), by the expert's form and by what the routing makes of the walk:
    many tiles of one expert, an expert no tile names (its gradients exactly
    zero), a last tile of one live row, no live tile at all."""
    routing, empty = LOADS[load]
    _kernels_against_loops(form, routing, 32, jnp.float32, 2e-6, empty,
                           monkeypatch)


@pytest.mark.parametrize("width", list(WIDTHS))
def test_kernels_match_the_loops_by_hidden_width(width, monkeypatch):
    form, h = WIDTHS[width]
    _kernels_against_loops(form, _even, h, jnp.float32, 2e-6, (),
                           monkeypatch)


@pytest.mark.parametrize("case", ["plain/even/32", "gated_biased/even/32",
                                  "gated/every_pair_on_one_expert/32",
                                  "plain_biased/one_row_in_a_last_tile/32",
                                  "gated_biased/even/200"])
def test_kernels_match_the_loops_in_bfloat16(case, monkeypatch):
    """The tolerance is one rounding of a cotangent to the activations'
    dtype before a product (the kernels' operands; the loops on the CPU
    multiply the float32 cotangent), 2^-8 of the largest value."""
    form, load, h = case.split("/")
    routing, empty = LOADS[load]
    _kernels_against_loops(form, routing, int(h), jnp.bfloat16, 2e-2, empty,
                           monkeypatch)


def _lowered(path):
    return sum(s["value"] for s in get_registry().series()
               if s["name"] == "ops/grouped_ffn_lowered"
               and s["labels"].get("path") == path)


def test_the_backend_and_the_shapes_choose_the_form(monkeypatch):
    """Off the TPU: the loops. Under the interpreter: the kernels. On a TPU:
    the kernels for the shapes `supports` names. The counter says which."""
    assert not kernels._on_tpu()
    before = _lowered("loop"), _lowered("pallas")
    _run("plain", _even, 32, jnp.float32, False, monkeypatch)
    assert (_lowered("loop"), _lowered("pallas")) == (before[0] + 1,
                                                      before[1])
    _run("plain", _even, 32, jnp.float32, True, monkeypatch)
    assert (_lowered("loop"), _lowered("pallas")) == (before[0] + 1,
                                                      before[1] + 1)
    monkeypatch.setattr(kernels, "FORCE_PALLAS_INTERPRET", False)
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    bf16 = jnp.bfloat16
    # the four cells' expert shapes, Nemotron's 14.5 lane tiles among them
    # (16,384 tokens a step, top-6, top-4 and top-8)
    for d, h, gated, k in ((2688, 1856, False, 6), (2048, 1536, True, 4),
                           (2048, 768, True, 8), (2048, 512, True, 8)):
        assert moe.grouped_path(d, h, gated, bf16, moe.TILE,
                                16384 * k) == "pallas"
    assert moe.grouped_path(2048, 512, True, jnp.float32, 256) == "pallas"
    # a row that is not whole lanes, a tile that is not whole chunks of
    # rows, a dtype a row does not carry, accumulators beyond VMEM, more
    # routed pairs than SMEM holds sorted ids of
    assert moe.grouped_path(2000, 512, True, bf16, 256) == "loop"
    assert moe.grouped_path(2048, 512, True, bf16, 12) == "loop"
    assert moe.grouped_path(2048, 512, True, jnp.float16, 256) == "loop"
    assert moe.grouped_path(4096, 4096, True, bf16, 256) == "loop"
    assert moe.grouped_path(2048, 512, True, bf16, 256, 24576 * 8) == "pallas"
    assert moe.grouped_path(2048, 512, True, bf16, 256, 32768 * 8) == "loop"


@pytest.mark.parametrize("d,h,gated,backward,buffers", [
    (2048, 512, True, True, 2), (2048, 768, True, True, 2),
    (2048, 1536, True, False, 2), (2688, 1856, False, False, 2)])
def test_an_expert_s_matrices_are_double_buffered_where_they_fit(
        d, h, gated, backward, buffers):
    mats = 3 if gated else 2
    assert kernels._weight_buffers(d, h, mats, 2, 256, backward) == buffers
    assert kernels._vmem_bytes(d, h, mats, 2, 256, backward,
                               buffers) <= kernels._VMEM_BUDGET


@pytest.mark.parametrize("n", [24, 600])
def test_a_row_carries_all_a_pair_needs_of_its_token(n):
    """The kernel that lays the rows out: activations, backward the
    cotangent, then the token's k weights on one more lane tile; 600 tokens
    are two whole steps of 256 and a part of one."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, 256), jnp.bfloat16)
    g = jnp.asarray(rng.randn(n, 256), jnp.float32)
    weight = jnp.asarray(rng.rand(n, 6), jnp.float32)
    rows = kernels._pack_rows(x, weight, interpret=True)
    assert rows.dtype == jnp.float32 and rows.shape == (n, 1, 256 + 128)
    assert np.array_equal(rows[:, 0, :256], x.astype(jnp.float32))
    assert np.array_equal(rows[:, 0, 256:262], weight)
    assert not np.any(np.asarray(rows[:, 0, 262:]))
    both = kernels._pack_rows(x, weight, g, interpret=True)
    assert both.shape == (n, 1, 2 * 256 + 128)
    assert np.array_equal(both[:, 0, :256], rows[:, 0, :256])
    assert np.array_equal(both[:, 0, 256:512], g)
    assert np.array_equal(both[:, 0, 512:], rows[:, 0, 256:])


def test_the_walk_s_scalars_follow_the_plan():
    """Live rows a tile (0 past the last live tile) and live tiles an
    expert, from the plan's own arrays."""
    idx = jnp.asarray(_one_row_in_the_last_tile(np.random.RandomState(0), N),
                      jnp.int32)
    plan = moe._dispatch(idx, 0, HELD, TILE)
    order, expert, lo, rows, n, tiles_of = kernels._tiles(plan, HELD, TILE)
    assert int(n[0]) == 4 and order is plan.order
    assert list(np.asarray(rows[:5])) == [1, TILE, TILE, 1, 0]
    assert list(np.asarray(expert[:4])) == [0, 2, 2, 2]
    assert list(np.asarray(tiles_of)) == [1, 0, 3, 0]
    assert not np.any(np.asarray(rows[4:]))
