"""Mamba-2's chunked state-space scan as two Pallas kernels.

No reference analog. The numbers are ops/ssm_ops.py's einsum form's (which
stays as the path off the TPU and as these kernels' oracle in the tests);
the difference is where the per-head [chunk, chunk] arrays live. The einsum
form writes the decays exp(segsum) and the weights C Bᵀ ∘ decay ∘ dt to HBM,
[B, chunks, H, chunk, chunk] float32 each, forward and again backward. Here
a grid step holds one chunk of one group of heads in VMEM and makes each
head's [chunk, chunk] tile, uses it and drops it.

Grid (B, G, chunks), the chunk axis last and sequential. A step loads the
group's x [L, R·P] (R heads of size P, side by side on the lanes), its B and
C [L, N], and the heads' log-decays dt·A and their dt, each [R, L] with the
positions on the lanes (`_rows`: one transpose of a [B, T, H] array in XLA,
whose own transpose gives the gradients of dt and A). Everything that is
one number a head and position is worked out in that form, all R heads in a
vreg: the log-decays' running sum along the chunk (seven rotations), the
decays from the chunk's start and to its end. A [L, L] decay tile needs
cum[l] - cum[s] with l down the sublanes and s across the lanes, so the step
turns those lines into columns once (a 128-square transpose through a VMEM
scratch); the backward kernel turns its column sums back the same way.

Heads narrower than a vreg's 128 lanes are taken 128 // P at a time: every
product of a head is made on its whole 128-lane tile of x (the MXU's width
either way) and the head's own lanes are picked from the result, so nothing
is sliced at 64 lanes.

*Forward*: the state entering a chunk is carried in a float32 VMEM scratch
[R·P, N], zeroed at chunk 0, and written out a chunk at a time for the
backward kernel ([B, chunks, G, R·P, N] float32). y = (C Bᵀ ∘ decay ∘ dt) x
+ exp(cum) ∘ (C S_inᵀ) + D x, cast to x's dtype; S_out = exp(total) S_in +
(x ∘ to_end)ᵀ B.

*Backward*: the same grid from the last chunk to the first, carrying the
cotangent of the state in the scratch; each head's tiles are made again
from the chunk's inputs and the stored entering state. dB and dC are summed
over a group's heads inside the step.

Precision: decays, dt, cumulative sums, the carried state and its cotangent
are float32. A product takes its operands as the einsum form's does: x, B,
C and the weights in x's dtype (bf16 under AMP) with float32 accumulation;
where one side is a float32 quantity (the state, a cotangent) both sides are
float32. (What the MXU makes of float32 operands is the compiler's default
on either path: one bf16 pass, in XLA's einsums and in Mosaic alike; PERF.md
section 6, PR 27.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._account import kernel_call

_LANES = 128
_F32 = jnp.float32

# Tests set this to run the kernels on the CPU through the Pallas
# interpreter. Nothing else turns the interpreter on.
FORCE_PALLAS_INTERPRET = False


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return FORCE_PALLAS_INTERPRET and not _on_tpu()


def supports(t: int, h: int, p: int, g: int, n: int, chunk: int) -> bool:
    """The shapes the kernels are written for: `chunk`, N and a group's R·P
    lanes multiples of 128, a head that divides a vreg's 128 lanes, T whole
    chunks, and at most 40 heads a group (the backward kernel keeps three
    columns a head side by side in one 128-lane tile)."""
    return (h % g == 0 and t % chunk == 0 and chunk % _LANES == 0
            and n % _LANES == 0 and (h // g * p) % _LANES == 0
            and _LANES % p == 0 and 3 * _lines(h // g) <= _LANES)


def takes(t: int, h: int, p: int, g: int, n: int, chunk: int) -> bool:
    """Whether the kernels run this scan: on a TPU (or under the tests'
    interpreter) and for the shapes `supports` names. Anything else is the
    einsum form's."""
    return ((_on_tpu() or FORCE_PALLAS_INTERPRET)
            and supports(t, h, p, g, n, chunk))


def _dot(a, b, ca: int, cb: int):
    """a · b contracting dim `ca` of a with dim `cb` of b, float32 result.
    Compiled, at Mosaic's own precision whatever
    `jax_default_matmul_precision` says (it refuses bf16 operands under
    "highest"); the interpreter follows the setting, as the einsum form
    does."""
    return lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=_F32,
        precision=None if _interpret() else lax.Precision.DEFAULT)


def _pick(mine, new, old):
    """`new` on a head's own lanes, `old` elsewhere."""
    return new if mine is None or old is None else jnp.where(mine, new, old)


def _sum_own(v, mine):
    """Σ of v [L, 128] over a head's own lanes, [L, 1]."""
    return jnp.sum(v if mine is None else jnp.where(mine, v, 0.0),
                   axis=1, keepdims=True)


def _sum_along_lanes(v, reverse=False):
    """Running sums of v [rows, L] along the lanes (each lane with the
    lanes before it; `reverse`: with the lanes after it), log2(L) rotations
    of the one or two vregs a group's heads fill."""
    n = v.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    k = 1
    while k < n:
        moved = pltpu.roll(v, n - k if reverse else k, v.ndim - 1)
        inside = lane < n - k if reverse else lane >= k
        v = v + jnp.where(inside, moved, 0.0)
        k *= 2
    return v


def _turned(v):
    """v [128, m·128] -> [m·128, 128], a 128-square at a time."""
    return jnp.concatenate(
        [v[:, k:k + _LANES].T for k in range(0, v.shape[1], _LANES)], axis=0)


def _lined(v):
    """v [m·128, 128] -> [128, m·128]: `_turned`'s inverse."""
    return jnp.concatenate(
        [v[k:k + _LANES].T for k in range(0, v.shape[0], _LANES)], axis=1)


def _lines(r: int) -> int:
    """The lines a group's R heads take in a stack of [R, L] arrays: R up to
    a whole sublane tile, so that every array starts on one."""
    return -(-r // 8) * 8


class _Chunk:
    """What both kernels read of a chunk, and the per-head pieces made from
    it. `r` heads of size `p` to the group.

    Whatever is one number a head and position — the log-decay summed along
    the chunk, the decays to the chunk's end — is worked out for all R heads
    at once with the positions on the lanes ([R, L]: one vreg where a column
    [L, 1] takes sixteen), and what the [L, ·] tiles need down the sublanes
    is turned into columns once, through `pad_scr`."""

    def __init__(self, x_ref, b_ref, c_ref, da_ref, dt_ref, pad_scr, r, p):
        self.r, self.p = r, p
        self.length = n = x_ref.shape[1]
        self.lo = x_ref.dtype
        self.bm, self.cm = b_ref[0], c_ref[0]              # [L, N]
        self.dt = dt_ref[0, 0]                             # [R, L]
        self.cum = _sum_along_lanes(da_ref[0, 0])          # [R, L]
        self.total = self.cum[:, n - 1:]                   # [R, 1]
        self.e = jnp.exp(self.cum)             # from the chunk's start
        self.to_end = jnp.exp(self.total - self.cum)
        self.te = self.to_end * self.dt
        pad_scr[0:r, :] = self.cum
        self.cum_col = _turned(pad_scr[...])               # [L, 128]
        self.cb = _dot(self.cm, self.bm, 1, 1)             # [L, L]: C Bᵀ
        self.causal = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
                       >= lax.broadcasted_iota(jnp.int32, (n, n), 1))
        self.lane = lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)

    @property
    def tiles(self):
        return self.r * self.p // _LANES

    @property
    def heads_a_tile(self):
        return _LANES // self.p

    def own(self, q):
        """Head q of a tile: its lanes of a [L, 128] tile; None when the
        tile is one head's."""
        if self.heads_a_tile == 1:
            return None
        return (self.lane >= q * self.p) & (self.lane < (q + 1) * self.p)

    def decay(self, h):
        """exp(cum[l] - cum[s]) for s <= l, 0 above the diagonal."""
        return jnp.exp(jnp.where(
            self.causal, self.cum_col[:, h:h + 1] - self.cum[h:h + 1],
            -1e30))

    def on_tile(self, v, j):
        """v [R, L] -> [128, L]: the line of each head of tile j, repeated
        down the head's P sublanes. Turned, it is the [L, 128] tile that
        scales x-like tiles a head and position at a time; its last lane,
        for `e`, is the decay over the whole chunk down the state's rows."""
        k = self.heads_a_tile
        return jnp.concatenate(
            [jnp.broadcast_to(v[h:h + 1], (self.p, self.length))
             for h in range(j * k, (j + 1) * k)], axis=0)


def _f32(*operands):
    """The two sides of a product of which one is a float32 quantity (the
    state, a cotangent)."""
    return tuple(v.astype(_F32) for v in operands)


def _fwd_kernel(x_ref, b_ref, c_ref, da_ref, dt_ref, d_ref, y_ref, st_ref,
                s_scr, pad_scr, *, r, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)
        pad_scr[...] = jnp.zeros_like(pad_scr)

    ch = _Chunk(x_ref, b_ref, c_ref, da_ref, dt_ref, pad_scr, r, p)
    for j in range(ch.tiles):
        sl = slice(j * _LANES, (j + 1) * _LANES)
        xt = x_ref[0, :, sl]                               # [L, 128]
        xf = xt.astype(_F32)
        s_in = s_scr[sl, :]                                # [128, N]
        st_ref[0, 0, 0, sl, :] = s_in
        y_in = None
        for q in range(ch.heads_a_tile):
            h = j * ch.heads_a_tile + q
            w = (ch.cb * ch.decay(h) * ch.dt[h:h + 1]).astype(ch.lo)
            y_in = _pick(ch.own(q), _dot(w, xt, 1, 0), y_in)
        e_lines = ch.on_tile(ch.e, j)
        y_off = _dot(*_f32(ch.cm, s_in), 1, 1) * _turned(e_lines)
        y = y_in + y_off + d_ref[:, sl] * xf
        y_ref[0, :, sl] = y.astype(y_ref.dtype)
        xw = (xf * _turned(ch.on_tile(ch.te, j))).astype(ch.lo)
        s_scr[sl, :] = (e_lines[:, ch.length - 1:] * s_in
                        + _dot(xw, ch.bm, 0, 0))


def _bwd_kernel(x_ref, b_ref, c_ref, da_ref, dt_ref, d_ref, g_ref, st_ref,
                dx_ref, db_ref, dc_ref, dda_ref, ddt_ref, dd_ref,
                ds_scr, pad_scr, *, r, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        pad_scr[...] = jnp.zeros_like(pad_scr)

    ch = _Chunk(x_ref, b_ref, c_ref, da_ref, dt_ref, pad_scr, r, p)
    n, lines = ch.length, _lines(r)
    head = lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    dcb = jnp.zeros((n, n), _F32)
    dc = jnp.zeros(ch.cm.shape, _F32)
    db = jnp.zeros(ch.bm.shape, _F32)
    # a head's sums along the lanes come out as columns [L, 1]: they are
    # kept side by side in `cols` (three a head: of the weights' gradient,
    # of y_off's and of to_end's) and turned into lines once, after the
    # heads; its sums down the sublanes are lines already
    cols = jnp.zeros((n, _LANES), _F32)
    dw_dt = jnp.zeros((r, n), _F32)            # Σ_l dW ∘ C Bᵀ ∘ decay
    ds_s = jnp.zeros((r, 1), _F32)             # Σ dS_out ∘ S_in, a head
    for j in range(ch.tiles):
        sl = slice(j * _LANES, (j + 1) * _LANES)
        xt, gt = x_ref[0, :, sl], g_ref[0, :, sl]          # [L, 128]
        xf, gf = xt.astype(_F32), gt.astype(_F32)
        s_in = st_ref[0, 0, 0, sl, :]                      # [128, N]
        ds_out = ds_scr[sl, :]
        z = _dot(*_f32(ch.cm, s_in), 1, 1)                 # [L, 128]
        dxw = _dot(*_f32(ch.bm, ds_out), 1, 1)             # [L, 128]
        gz, dte = gf * z, dxw * xf
        s_ds = jnp.sum(ds_out * s_in, axis=1, keepdims=True)   # [128, 1]
        dx_in = None
        for q in range(ch.heads_a_tile):
            h = j * ch.heads_a_tile + q
            mine = ch.own(q)
            decay, dt_r = ch.decay(h), ch.dt[h:h + 1]
            cbd = ch.cb * decay
            w = (cbd * dt_r).astype(ch.lo)
            g_own = gt if mine is None else jnp.where(mine, gt, 0)
            dw = _dot(g_own, xt, 1, 1)                     # [L, L]
            dx_in = _pick(mine, _dot(w, gt, 0, 0), dx_in)
            dcb = dcb + dw * decay * dt_r
            dq = dw * cbd
            dw_dt = jnp.where(head == h,
                              jnp.sum(dq, axis=0, keepdims=True), dw_dt)
            for i, v in enumerate((
                    jnp.sum(dq * dt_r, axis=1, keepdims=True),
                    _sum_own(gz, mine), _sum_own(dte, mine))):
                cols = jnp.where(ch.lane == i * lines + h, v, cols)
            ds_s = jnp.where(head == h, jnp.sum(
                s_ds[q * p:(q + 1) * p], axis=0, keepdims=True), ds_s)
        e_lines = ch.on_tile(ch.e, j)
        te_t = _turned(ch.on_tile(ch.te, j))
        ge = gf * _turned(e_lines)
        dc = dc + _dot(*_f32(ge, s_in), 1, 0)              # [L, N]
        ds_scr[sl, :] = (e_lines[:, n - 1:] * ds_out
                         + _dot(*_f32(ge, ch.cm), 0, 0))
        xw = (xf * te_t).astype(ch.lo)
        db = db + _dot(*_f32(xw, ds_out), 1, 0)            # [L, N]
        dx = dx_in + dxw * te_t + d_ref[:, sl] * gf
        dx_ref[0, :, sl] = dx.astype(dx_ref.dtype)
        dd_ref[0, 0, 0, :, sl] = jnp.sum(gf * xf, axis=0, keepdims=True)
    dc = dc + _dot(*_f32(dcb, ch.bm), 1, 0)
    db = db + _dot(*_f32(dcb, ch.cm), 0, 0)
    dc_ref[0] = dc.astype(dc_ref.dtype)
    db_ref[0] = db.astype(db_ref.dtype)
    # the rest is a few vregs' work, the positions on the lanes: what the
    # weights, y_off = e ∘ (C S_inᵀ), to_end ∘ dt and S_out's exp(total) S_in
    # give cum and dt
    lined = _lined(cols)
    d_w, d_e, d_te = (lined[i * lines:i * lines + r] for i in range(3))
    v = d_te * ch.te
    dtotal = (jnp.sum(v, axis=1, keepdims=True)
              + jnp.exp(ch.total) * ds_s)                  # [R, 1]
    at_end = lax.broadcasted_iota(jnp.int32, (r, n), 1) == n - 1
    dcum = (d_w + d_e * ch.e - v - dw_dt * ch.dt
            + jnp.where(at_end, dtotal, 0.0))
    dda_ref[0, 0] = _sum_along_lanes(dcum, reverse=True)
    ddt_ref[0, 0] = d_te * ch.to_end + dw_dt


def _rows(dt, a, g):
    """dt [B, T, H], a [H] -> the kernels' log-decays dt·A and dt, each
    [B, G, R, T]: a head a line, the positions on the lanes."""
    bsz, t, h = dt.shape
    both = jnp.stack([dt * a, dt]).reshape(2, bsz, t, g, h // g)
    return tuple(both.transpose(0, 1, 3, 4, 2))


def _specs(rp, n, r, chunk, order):
    """Block specs of a chunk's x-like, B-like, dt-like, D, state and dD
    arrays; `order` maps the grid's chunk index to the chunk."""
    return {
        "x": pl.BlockSpec((1, chunk, rp), lambda b, i, z: (b, order(z), i)),
        "bc": pl.BlockSpec((1, chunk, n), lambda b, i, z: (b, order(z), i)),
        "dt": pl.BlockSpec((1, 1, r, chunk),
                           lambda b, i, z: (b, i, 0, order(z))),
        "d": pl.BlockSpec((1, rp), lambda b, i, z: (0, i)),
        "state": pl.BlockSpec((1, 1, 1, rp, n),
                              lambda b, i, z: (b, order(z), i, 0, 0)),
        "dd": pl.BlockSpec((1, 1, 1, 1, rp),
                           lambda b, i, z: (b, order(z), i, 0, 0)),
    }


_SEMANTICS = ("parallel", "parallel", "arbitrary")


# `_forward` and `_backward` are jitted so that a model's mixers (and a remat
# block's second forward) share one trace and one lowering of each kernel:
# the unrolled bodies are what costs a step's set-up. `interpret` is in the
# key because the tests turn the interpreter on and off.
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _forward(x, dt, a, b, c, d, *, chunk, interpret):
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, nc, rp = h // g, t // chunk, h // g * p
    sp = _specs(rp, n, r, chunk, lambda z: z)
    y, states = kernel_call(
        "ssd_fwd", functools.partial(_fwd_kernel, r=r, p=p),
        grid=(bsz, g, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sp["dt"], sp["d"]],
        out_specs=[sp["x"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, g, rp, n), _F32)],
        scratch_shapes=[pltpu.VMEM((rp, n), _F32),
                        pltpu.VMEM((_LANES, chunk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="ssd_scan_fwd",
    )(x.reshape(bsz, t, h * p), b.reshape(bsz, t, g * n),
      c.reshape(bsz, t, g * n), *_rows(dt, a, g),
      jnp.repeat(d.astype(_F32), p)[None])
    return y.reshape(x.shape), states


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward(x, dt, a, b, c, d, states, gy, *, chunk, interpret):
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, nc, rp = h // g, t // chunk, h // g * p
    rows, rows_vjp = jax.vjp(lambda dt, a: _rows(dt, a, g), dt, a)
    sp = _specs(rp, n, r, chunk, lambda z: nc - 1 - z)
    dx, db, dc, dda, ddt, dd = kernel_call(
        "ssd_bwd", functools.partial(_bwd_kernel, r=r, p=p),
        grid=(bsz, g, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sp["dt"], sp["d"],
                  sp["x"], sp["state"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sp["dt"],
                   sp["dd"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, t, g * n), b.dtype),
                   jax.ShapeDtypeStruct((bsz, t, g * n), c.dtype),
                   jax.ShapeDtypeStruct(rows[0].shape, _F32),
                   jax.ShapeDtypeStruct(rows[1].shape, _F32),
                   jax.ShapeDtypeStruct((bsz, nc, g, 1, rp), _F32)],
        scratch_shapes=[pltpu.VMEM((rp, n), _F32),
                        pltpu.VMEM((_LANES, chunk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="ssd_scan_bwd",
    )(x.reshape(bsz, t, h * p), b.reshape(bsz, t, g * n),
      c.reshape(bsz, t, g * n), *rows,
      jnp.repeat(d.astype(_F32), p)[None],
      gy.reshape(bsz, t, h * p), states)
    ddt, da = rows_vjp((dda, ddt))
    dd = jnp.sum(dd.reshape(bsz * nc, h, p), axis=(0, 2)).astype(d.dtype)
    return (dx.reshape(x.shape), ddt, da, db.reshape(b.shape),
            dc.reshape(c.shape), dd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_scan(x, dt, a, b, c, d, chunk):
    """x [B, T, H, P]; dt [B, T, H] float32 (> 0); a [H] float32 (< 0);
    b, c [B, T, G, N]; d [H]. Returns the scan plus d ∘ x, [B, T, H, P] in
    x's dtype. The caller has asked `takes`."""
    return _scan_fwd(x, dt, a, b, c, d, chunk)[0]


def _scan_fwd(x, dt, a, b, c, d, chunk):
    y, states = _forward(x, dt, a, b, c, d, chunk=chunk,
                         interpret=_interpret())
    return y, (x, dt, a, b, c, d, states)


def _scan_bwd(chunk, res, gy):
    return _backward(*res, gy, chunk=chunk, interpret=_interpret())


ssd_scan.defvjp(_scan_fwd, _scan_bwd)
