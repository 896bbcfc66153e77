"""The chunked gated delta rule (Kimi Delta Attention) as two Pallas kernels.

No reference analog. The numbers are ops/linear_attn_ops.py's einsum form's
(which stays as the path off the TPU, under a mesh, for other shapes, and as
these kernels' oracle in the tests); the difference is where a chunk's terms
live. The einsum form makes them for 512 chunks-times-heads at a time in HBM
inside two nested `lax.scan`s; here a grid step holds one *tile* of a few
heads in VMEM, `TILE` = 128 positions = two chunks of 64 one below the other,
makes their terms, walks the state through them and drops everything but the
result.

Grid (B, H / heads a step, T / 128), the last axis sequential. q, k, v and
the gate's values are read where they lie, [B, T, H·128] with a head's 128
channels on the lanes (no transpose to heads first); beta, one number a head
and position, comes as lines [1, 128] with the positions on the lanes and is
turned into a column inside (a masked lane sum). A step's heads (`_heads`:
four) are worked *stage by stage side by side* (`_each`): a tile is a chain
of dependent products (the inverse alone is ten, each some 300 cycles from
its first push to its last pop), Mosaic's scheduler does not interleave two
heads whose code follows one another, and written a stage across the heads
at a time one head's product runs in the wait for another's (13.4 ms a
layer's forward call at four heads for 22.4 at one; PERF.md section 6,
PR 48).

**A tile's terms** (`_tiles_of`; every array [128, 128], a position a row):

- the l2 normalisation and the log-decay `-exp(A_log) softplus(raw +
  dt_bias)`, float32;
- the running sum along each chunk: one product with a block-triangular
  matrix of ones at full float32 precision;
- the decayed triangles A_qk and A_kk *without a positive exponent*: level by
  level. At level s (32, 16, 8, 4, 2, 1) the chunk is cut into blocks of 2s
  positions and the entries with the row in a block's upper half and the
  column in its lower half are one product on the matrix unit, rows decayed
  from the block's middle position m (`exp(G_r - G_m)`, r >= m) and columns
  up to it (`exp(G_m - G_i)`, i < m): both exponents <= 0 whatever g is, and
  clamped there for the rows and columns the level does not use. Six
  products of [256, 128] (q's rows over k's) by [128, 128] (both chunks'
  columns: the other chunk's quarter is masked away) and a seventh for the
  diagonal give every entry exactly once, their operands rounded to q's dtype
  as the einsum form's off-diagonal blocks are;
- `[W | U] = (I + Diag(beta) A_kk)^-1 Diag(beta) [K+ | V]`: N = Diag(beta)
  A_kk is strictly lower triangular in each chunk. The inverse is made block
  by block, the diagonal blocks of 2, 4, ..., 64 positions in turn
  (`_inverse`: block forward substitution as masked products of the two
  chunks' block-diagonal [128, 128]): ten products at full float32
  precision, no dependent rows. Its derivative is written by hand (two more
  products with the inverse at hand).

**The walk**: the state a head is kept transposed, [V, K] float32, so that a
chunk's decay (one number a key channel) lies along the lanes. Chunk by
chunk `V_new = U - W S`, the chunk's output, `S <- decay S + K_end^T V_new`,
as `_chunk_step` of the einsum form.

*Forward*: the state is carried in a VMEM scratch, zeroed at tile 0, and the
state entering each tile is written out ([B, H, T / 128, V, K] float32: half
of what the einsum form keeps) with the result and the running minimum of
the chunks' total log-decays (the decay floor).

*Backward*: the same grid from the last tile to the first, the state's
cotangent in the scratch; a step is `jax.vjp` of the tile's function at its
inputs and stored entering state, traced into the kernel's body: the terms
are made again and differentiated in VMEM. The cotangents of q, k, v and the
gate's values are written a tile at a time, beta's as a line, and A_log's
and dt_bias's are summed over a head's tiles in the output block and reduced
outside.

Precision: as the einsum form's. Decays, beta, cumulative sums, the system,
its inverse and the states in float32; the matrix products' operands in q's
dtype with float32 accumulation; the running sum's and the inverse's
products at full float32 precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.remat import kept
from ._account import kernel_call

TILE = 128                      # positions a grid step's unit: two chunks
CHUNK = 64
_LANES = 128
_LEVELS = (32, 16, 8, 4, 2, 1)
_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
HEADS = 4                       # heads a grid step, at most

# Tests set this to run the kernels on the CPU through the Pallas
# interpreter. Nothing else turns the interpreter on.
FORCE_PALLAS_INTERPRET = False


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return FORCE_PALLAS_INTERPRET and not _on_tpu()


def supports(t: int, k: int, v: int, chunk: int, dtype) -> bool:
    """The shapes the kernels are written for: heads of 128 key and 128
    value channels (a vreg's lanes, the matrix unit's width), chunks of 64,
    T whole tiles of two chunks, bf16 or float32 operands."""
    return (k == _LANES and v == _LANES and chunk == CHUNK and t % TILE == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def takes(t: int, k: int, v: int, chunk: int, dtype) -> bool:
    """Whether the kernels run this rule: on a TPU (or under the tests'
    interpreter) and for the shapes `supports` names. Anything else is the
    einsum form's."""
    return ((_on_tpu() or FORCE_PALLAS_INTERPRET)
            and supports(t, k, v, chunk, dtype))


def _heads(h: int) -> int:
    """The heads a grid step takes side by side: the more, the better one
    head's chain of dependent products hides in another's (b2 x T8192, 32
    heads, forward / with backward, ms: 22.4 / 55.3 at 1, 14.6 / 38.7 at 2,
    13.4 / 35.5 at 4, 13.1 / 34.2 at 8, whose backward kernel compiles in
    39 s for 4's 25; PERF.md section 6, PR 48)."""
    return next(n for n in (HEADS, 2, 1) if h % n == 0)


def _dot(a, b, ca: int, cb: int, exact: bool = False):
    """a . b contracting dim `ca` of a with dim `cb` of b, float32 result.
    `exact`: at full float32 precision. Otherwise compiled at Mosaic's own
    precision whatever `jax_default_matmul_precision` says (it refuses bf16
    operands under "highest"); the interpreter follows the setting, as the
    einsum form does."""
    precision = _HI if exact else (
        None if _interpret() else lax.Precision.DEFAULT)
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=_F32, precision=precision)


def _middles(cum, s: int):
    """cum [R, K] -> for each position the cumulative log-decay at the middle
    position (the first of the upper half) of its block of 2s positions."""
    r, k = cum.shape
    if s >= 4:
        blocks = cum.reshape(r // (2 * s), 2 * s, k)
        return jnp.broadcast_to(blocks[:, s:s + 1, :],
                                blocks.shape).reshape(r, k)
    eights = cum.reshape(r // 8, 8, k)
    line = lax.broadcasted_iota(jnp.int32, eights.shape, 1)
    pick = lambda j: jnp.broadcast_to(eights[:, j:j + 1, :], eights.shape)
    if s == 2:
        mid = jnp.where(line < 4, pick(2), pick(6))
    else:
        mid = jnp.where(line < 2, pick(1), jnp.where(
            line < 4, pick(3), jnp.where(line < 6, pick(5), pick(7))))
    return mid.reshape(r, k)


def _unit(x, eps):
    xf = x.astype(_F32)
    norm = jnp.sqrt(jnp.sum(xf * xf, axis=-1, keepdims=True))
    return (xf / jnp.maximum(norm, eps)).astype(x.dtype)


def _each(f, *columns):
    """[f(a, b, ...) for the heads' a, b, ...]: one stage of a tile for every
    head of the grid step before the next stage of any, so that the heads'
    chains of dependent products lie side by side in the program and one
    head's product runs in the wait for another's."""
    return [f(*args) for args in zip(*columns)]


def _tiles_of(scale: float, l2_eps: float, differentiated: bool):
    """The function of one tile of R = `TILE` positions of a grid step's
    heads (its docstring below), with the masks the heads share made once.
    `differentiated`: with the hand-written derivatives of the running sum
    and the solve bound (the forward kernel leaves them unbound: Mosaic
    lowers no `custom_vjp` call that closes over the masks)."""
    r = TILE
    row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    eye = row == col
    # ones where the column's position is in the row's chunk and not after it
    below = jnp.where((row // CHUNK == col // CHUNK) & (col <= row), 1.0, 0.0)
    # level s: the row in the upper half and the column in the lower half of
    # the same block of 2s positions
    halves = {s: ((row // (2 * s) == col // (2 * s))
                  & (row & s != 0) & (col & s == 0)) for s in _LEVELS}

    def running_sums(gs):
        """g [R, K] float32 a head -> its running sum along each chunk: one
        product with a block-triangular matrix of ones at full precision."""
        return _each(lambda g: _dot(below, g, 1, 0, exact=True), gs)

    def inverses(ns):
        """(I + n)^-1 a head, for n strictly lower triangular within chunks:
        the inverses of the diagonal blocks of 2, 4, ..., 64 positions in
        turn, each from the last. With X the inverses of the blocks of s and
        C the entries of n between the two halves of each block of 2s, the
        blocks' inverses are X - X C X (block forward substitution; no power
        of n is formed, so nothing grows large to cancel again: with one key
        repeated at beta 1 n's powers reach 1e17 and the inverse is +-1).
        Two products of the tile's block-diagonal [R, R] a level, ten in
        all, no dependent rows."""
        xs = _each(lambda n: jnp.where(eye, 1.0, 0.0)
                   - jnp.where(halves[1], n, 0.0), ns)
        for s in _LEVELS[-2::-1]:
            ts = _each(lambda n, x: _dot(jnp.where(halves[s], n, 0.0), x, 1,
                                         0, exact=True), ns, xs)
            xs = _each(lambda x, t: x - _dot(x, t, 1, 0, exact=True), xs, ts)
        return xs

    def solves(ns, rhss):
        """(I + n)^-1 rhs a head, rhs [R, N], float32, at full precision."""
        return solves_fwd(ns, rhss)[0]

    def solves_fwd(ns, rhss):
        invs = inverses(ns)
        outs = _each(lambda x, rhs: _dot(x, rhs, 1, 0, exact=True), invs,
                     rhss)
        return outs, (invs, outs)

    def solves_bwd(res, d_outs):
        invs, outs = res
        d_rhss = _each(lambda x, d: _dot(x, d, 0, 0, exact=True), invs,
                       d_outs)
        return _each(lambda d, out: -_dot(d, out, 1, 1, exact=True), d_rhss,
                     outs), d_rhss

    if differentiated:
        running_sums, solves = map(jax.custom_vjp, (running_sums, solves))
        running_sums.defvjp(
            lambda gs: (_each(lambda g: _dot(below, g, 1, 0, exact=True),
                              gs), None),
            lambda _, ds: (_each(lambda d: _dot(below, d, 0, 0, exact=True),
                                 ds),))
        solves.defvjp(solves_fwd, solves_bwd)

    def tiles(qs, ks, vs, gs, betas, gates, states):
        """One tile of each head; a list a head of: q, k, v [R, 128] in the
        operands' dtype; g [R, 128] the log-decay, or with `gates` a head's
        (-exp(A_log) [1, 128] a lane, dt_bias [1, 128]) the decay gate's raw
        values; beta [1, R] float32, the positions on the lanes; the state
        entering the tile [V, K] float32, transposed. Returns (the tiles'
        outputs [R, 128] in q's dtype, the states leaving them), and aside
        the least total log-decay of a tile's chunks a key channel
        [1, 128]."""
        lo, dk = qs[0].dtype, ks[0].shape[1]
        if l2_eps:
            qs, ks = (_each(lambda x: _unit(x, l2_eps), xs)
                      for xs in (qs, ks))
        gs = _each(lambda g: g.astype(_F32), gs)
        if gates is not None:
            gs = _each(lambda g, gate: gate[0] * jax.nn.softplus(g + gate[1]),
                       gs, gates)
        cums = running_sums(gs)
        qfs, kfs = (_each(lambda x: x.astype(_F32), xs) for xs in (qs, ks))

        # the decayed triangles, level by level (the module docstring)
        a_qks = _each(lambda q, k: jnp.where(eye, _dot(q, k, 1, 1), 0.0),
                      qs, ks)
        a_kks = [jnp.zeros((r, r), _F32)] * len(qs)
        for s in _LEVELS:
            ds = _each(lambda cum: cum - _middles(cum, s), cums)
            lefts = _each(
                lambda d, qf, kf: jnp.concatenate(
                    [(x * jnp.exp(jnp.minimum(d, 0.0))).astype(lo)
                     for x in (qf, kf)], axis=0), ds, qfs, kfs)
            rights = _each(
                lambda d, kf: (kf * jnp.exp(jnp.minimum(-d, 0.0))).astype(lo),
                ds, kfs)
            ps = _each(lambda a, b: _dot(a, b, 1, 1), lefts, rights)  # [2R, R]
            a_qks = _each(lambda p, a: jnp.where(halves[s], p[:r], a), ps,
                          a_qks)
            a_kks = _each(lambda p, a: jnp.where(halves[s], p[r:], a), ps,
                          a_kks)

        # [W | U] = (I + Diag(beta) A_kk)^-1 Diag(beta) [K exp(G) | V]
        b_cols = _each(lambda beta: jnp.sum(jnp.where(eye, beta, 0.0), axis=1,
                                            keepdims=True), betas)
        downs = _each(jnp.exp, cums)
        wus = solves(
            _each(lambda b, a: b * a, b_cols, a_kks),
            _each(lambda b, kf, down, v: b * jnp.concatenate(
                [kf * down, v.astype(_F32)], axis=1), b_cols, kfs, downs, vs))
        ws = _each(lambda wu: wu[:, :dk].astype(lo), wus)
        us = _each(lambda wu: wu[:, dk:], wus)
        q_downs = _each(lambda qf, down: (qf * down * scale).astype(lo), qfs,
                        downs)
        ends = _each(lambda cum: cum.reshape(r // CHUNK, CHUNK, dk)[
            :, CHUNK - 1:, :], cums)                            # [., 1, K]
        k_ends = _each(
            lambda kf, cum, end: (kf * jnp.exp(jnp.broadcast_to(
                end, (r // CHUNK, CHUNK, dk)).reshape(r, dk) - cum)).astype(
                    lo), kfs, cums, ends)
        decays = _each(jnp.exp, ends)

        # the walk: the states [V, K] through the tile's chunks
        from_states, v_news = [], []            # a list a chunk, of the heads'
        for c in range(r // CHUNK):
            at = slice(c * CHUNK, (c + 1) * CHUNK)
            s_los = _each(lambda state: state.astype(lo), states)
            vls = _each(lambda u, w, s_lo: (u[at] - _dot(
                w[at], s_lo, 1, 1)).astype(lo), us, ws, s_los)
            from_states.append(_each(
                lambda q_down, s_lo: _dot(q_down[at], s_lo, 1, 1), q_downs,
                s_los))
            states = _each(
                lambda decay, state, vl, k_end: decay[c] * state + _dot(
                    vl, k_end[at], 0, 0), decays, states, vls, k_ends)
            v_news.append(vls)
        outs = _each(
            lambda from_state, a_qk, v_new: (
                jnp.concatenate(from_state, axis=0) + _dot(
                    (a_qk * scale).astype(lo),
                    jnp.concatenate(v_new, axis=0), 1, 0)).astype(lo),
            zip(*from_states), a_qks, zip(*v_news))
        floors = _each(lambda end: lax.stop_gradient(jnp.min(end, axis=0)),
                       ends)
        return (outs, states), floors

    return tiles


def _head(j):
    return slice(j * _LANES, (j + 1) * _LANES)


def _heads_inputs(refs, heads, gated):
    """(what `tiles` takes before the states, a list a head each, from a
    kernel's first five or seven refs; the refs after them)."""
    n = 7 if gated else 5
    js = range(heads)
    wide = [[ref[0, :, _head(j)] for j in js] for ref in refs[:4]]
    gates = [tuple(ref[j] for ref in refs[5:n]) for j in js] if gated else None
    return (*wide, [refs[4][0, j, 0] for j in js], gates), refs[n:]


def _fwd_kernel(*refs, heads, gated, scale, l2_eps):
    inputs, (o_ref, st_ref, floor_ref, s_scr) = _heads_inputs(refs, heads,
                                                              gated)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)
        floor_ref[...] = jnp.zeros_like(floor_ref)

    states = [s_scr[j] for j in range(heads)]
    for j, state in enumerate(states):
        st_ref[0, j, 0] = state
    (outs, states), floors = _tiles_of(scale, l2_eps, False)(*inputs, states)
    for j in range(heads):
        o_ref[0, :, _head(j)] = outs[j]
        s_scr[j] = states[j]
        floor_ref[0, j] = jnp.minimum(floor_ref[0, j], floors[j])


def _bwd_kernel(*refs, heads, gated, scale, l2_eps):
    inputs, (do_ref, st_ref, *out_refs, ds_scr) = _heads_inputs(refs, heads,
                                                                gated)
    d_refs, dbeta_ref, dgate_refs = out_refs[:4], out_refs[4], out_refs[5:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        for ref in dgate_refs:
            ref[...] = jnp.zeros_like(ref)

    js = range(heads)
    _, vjp, _ = jax.vjp(_tiles_of(scale, l2_eps, True), *inputs,
                        [st_ref[0, j, 0] for j in js], has_aux=True)
    *d_ins, dbetas, dgates, ds_ins = vjp(([do_ref[0, :, _head(j)] for j in js],
                                          [ds_scr[j] for j in js]))
    for j in js:
        for ref, ds in zip(d_refs, d_ins):
            ref[0, :, _head(j)] = ds[j]
        dbeta_ref[0, j, 0] = dbetas[j]
        ds_scr[j] = ds_ins[j]
        for ref, d in zip(dgate_refs, dgates[j] if gated else ()):
            ref[0, j] += d


def _specs(heads, order):
    """Block specs of a tile's q-like arrays ([B, T, H·128]), beta-like lines
    ([B, H, tiles, 1, 128]), the gate's lines ([H, 1, 128]), the states
    ([B, H, tiles, V, K]) and what is summed over a head's tiles
    ([B, H, 1, 128]); `order` maps the grid's last index to the tile."""
    wide = heads * _LANES
    return {
        "x": pl.BlockSpec((1, TILE, wide), lambda b, h, z: (b, order(z), h)),
        "line": pl.BlockSpec((1, heads, 1, 1, TILE),
                             lambda b, h, z: (b, h, order(z), 0, 0)),
        "gate": pl.BlockSpec((heads, 1, _LANES), lambda b, h, z: (h, 0, 0)),
        "state": pl.BlockSpec((1, heads, 1, _LANES, _LANES),
                              lambda b, h, z: (b, h, order(z), 0, 0)),
        "sum": pl.BlockSpec((1, heads, 1, _LANES),
                            lambda b, h, z: (b, h, 0, 0)),
    }


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"),
               vmem_limit_bytes=96 * 2 ** 20)


def _lines(beta):
    """beta [B, T, H] -> [B, H, T / 128, 1, 128]: a head's positions on the
    lanes, a tile a line."""
    b, t, h = beta.shape
    return jnp.swapaxes(beta, 1, 2).reshape(b, h, t // TILE, 1, TILE)


def _gate_lines(gate):
    """(A_log [H], dt_bias [H, K]) -> (-exp(A_log) on every lane, dt_bias),
    [H, 1, 128] float32 each."""
    a_log, dt_bias = gate
    a = -jnp.exp(a_log.astype(_F32))
    return (jnp.broadcast_to(a[:, None, None], (a.shape[0], 1, _LANES)),
            dt_bias.astype(_F32)[:, None, :])


# `_forward` and `_backward` are jitted so that a model's layers (and a remat
# block's second forward) share one trace and one lowering of each kernel.
@functools.partial(jax.jit,
                   static_argnames=("scale", "l2_eps", "interpret"))
def _forward(q, k, v, g, beta, gate, *, scale, l2_eps, interpret):
    b, t, h, d = q.shape
    heads, tiles = _heads(h), t // TILE
    sp = _specs(heads, lambda z: z)
    flat = lambda x: x.reshape(b, t, h * d)
    gate_in = () if gate is None else _gate_lines(gate)
    out, states, floor = kernel_call(
        "kda_fwd",
        functools.partial(_fwd_kernel, heads=heads, gated=gate is not None,
                          scale=scale, l2_eps=l2_eps),
        grid=(b, h // heads, tiles),
        in_specs=[sp["x"]] * 4 + [sp["line"]] + [sp["gate"]] * len(gate_in),
        out_specs=[sp["x"], sp["state"], sp["sum"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, tiles, d, d), _F32),
                   jax.ShapeDtypeStruct((b, h, 1, d), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, d, d), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(flat(q), flat(k), flat(v), flat(g), _lines(beta), *gate_in)
    return out.reshape(q.shape), jnp.min(floor), states


@functools.partial(jax.jit,
                   static_argnames=("scale", "l2_eps", "interpret"))
def _backward(q, k, v, g, beta, gate, states, d_out, *, scale, l2_eps,
              interpret):
    b, t, h, d = q.shape
    heads, tiles = _heads(h), t // TILE
    sp = _specs(heads, lambda z: tiles - 1 - z)
    flat = lambda x: x.reshape(b, t, h * d)
    wide = lambda x: jax.ShapeDtypeStruct((b, t, h * d), x.dtype)
    gate_in, gate_vjp = (), None
    if gate is not None:
        gate_in, gate_vjp = jax.vjp(_gate_lines, gate)
    lines = _lines(beta)
    sums = [jax.ShapeDtypeStruct((b, h, 1, d), _F32)] * len(gate_in)
    dq, dk, dv, dg, dbeta, *dgate = kernel_call(
        "kda_bwd",
        functools.partial(_bwd_kernel, heads=heads, gated=gate is not None,
                          scale=scale, l2_eps=l2_eps),
        grid=(b, h // heads, tiles),
        in_specs=([sp["x"]] * 4 + [sp["line"]]
                  + [sp["gate"]] * len(gate_in) + [sp["x"], sp["state"]]),
        out_specs=[sp["x"]] * 4 + [sp["line"]] + [sp["sum"]] * len(gate_in),
        out_shape=[wide(q), wide(k), wide(v), wide(g),
                   jax.ShapeDtypeStruct(lines.shape, _F32)] + sums,
        scratch_shapes=[pltpu.VMEM((heads, d, d), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="kda_chunk_bwd",
    )(flat(q), flat(k), flat(v), flat(g), lines, *gate_in,
      flat(d_out.astype(q.dtype)), states)
    dbeta = jnp.swapaxes(dbeta.reshape(b, h, t), 1, 2)
    d_gate = None
    if gate is not None:
        (d_gate,) = gate_vjp(tuple(jnp.sum(x, axis=0) for x in dgate))
    shaped = lambda x: x.reshape(q.shape)
    return shaped(dq), shaped(dk), shaped(dv), shaped(dg), dbeta, d_gate


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def kda_rule(q, k, v, g, beta, gate, scale, l2_eps):
    """`linear_attn_ops.kda_rule` at a chunk of 64 by the kernels: q, k, v
    [B, T, H, 128] in one dtype, g [B, T, H, 128], beta [B, T, H] float32,
    `gate` None or (A_log [H], dt_bias [H, 128]). Returns (o in q's dtype,
    the decay floor). The caller has asked `takes`."""
    return _rule_fwd(q, k, v, g, beta, gate, scale, l2_eps)[0]


def _rule_fwd(q, k, v, g, beta, gate, scale, l2_eps):
    out, floor, states = _forward(q, k, v, g, beta, gate, scale=scale,
                                  l2_eps=l2_eps, interpret=_interpret())
    out = kept(out, "gated_delta_rule/out")
    states = kept(states, "gated_delta_rule/states")
    return (out, floor), (q, k, v, g, beta, gate, states)


def _rule_bwd(scale, l2_eps, res, cotangents):
    # the floor carries no gradient
    return _backward(*res, cotangents[0], scale=scale, l2_eps=l2_eps,
                     interpret=_interpret())


kda_rule.defvjp(_rule_fwd, _rule_bwd)
