"""The experts' grouped product (parallel/moe.py) as Pallas kernels.

No reference analog. The numbers are `moe._loop_fwd` / `_loop_bwd`'s (the
loops over tiles, which stay as the path off the TPU and as these kernels'
oracle in the tests); the difference is who moves a tile's rows and where a
weight gradient is summed.

Both kernels walk the plan's tiles on a grid of `max_tiles` sequential
steps. The plan's sorted pair ids, each tile's expert, first position and
live rows, the number of live tiles and each expert's are scalar-prefetched;
a step past the last live tile does nothing and fetches nothing (its index
maps name the last live tile's blocks again). The expert's matrices come by
BlockSpecs whose index is the tile's expert, so the consecutive tiles of one
expert fetch them once, the hidden width a block's whole extent; where it is
not whole lane tiles (Nemotron's 1,856) W1 and W3 are held turned, [H, D],
which is how XLA lays such a parameter out anyway (`_turned`).

*Rows.* A tile's rows are scattered over the tokens, and a DMA moves whole
memory tiles, so the arrays a row is copied from or to are laid out a row a
memory tile: float32 `[N, 1, W]`. One small kernel a call writes the rows in
(`_pack_rows`): a token's activations, backward its cotangent beside them,
and its k combine weights on one more lane tile, so that one copy brings all
a pair needs of its token, and which of the k lanes is the pair's is noted
from its id while the copy is started. Starting a copy costs the scalar unit
about 26 ns whatever the row's size and waiting for eight about as much as
for one, so a tile's copies go eight a loop step and are waited for eight a
wait. A step starts the next tile's rows into the other half of a double
buffer before it multiplies. The rows out are sums over a token's pairs: a
tile's tokens are all different (one expert, and a token's experts differ),
so a step reads the tile's rows of the float32 result (zeroed by the
kernel's first step), adds its own and writes them back; the steps are
sequential and a step waits for the one before it to have written. The last
chunk's rows past the tile's last live one are written to spare rows.

*Forward*: `o = (act(x·W1 + b1) [⊙ x·W3])·W2 + b2`, times the pair's weight,
the hidden width at most 512 lanes at a time.

*Backward*: the hidden activations are made again; `dx` rows go out like the
forward's with the pair weight's gradient on the pair's own lane of one more
lane tile, and the gradients of the matrices and biases are summed in
float32 VMEM scratch over the tiles of one expert and copied out once, at
the expert's last tile (an expert with no tile gets zeros at the first
step).

Precision is the loops': operands in x's dtype, every product accumulated in
float32, the gate's product of float32 halves, the cotangent taken in float32
and rounded to x's dtype where it is a product's operand.
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
_H_BLOCK = 512    # lanes of the hidden width a product takes at once
_CHUNK = 8        # rows whose copies one loop step starts and one wait takes
_SPARE = 8        # rows past the last token, where a chunk's dead rows go
_PACK_ROWS = 256  # tokens a step of the kernel that lays the rows out
_PREFETCHED = 6   # the scalar arrays `_tiles` gives a walk
# of a v5e's 128 MiB of VMEM: what a kernel may plan for its buffers, and the
# limit it asks Mosaic for, whose own scratch is the rest
_VMEM_BUDGET = 96 * 2 ** 20
_VMEM_LIMIT = 110 * 2 ** 20
# the routed pairs whose sorted ids (an int32 each, prefetched) a v5e's 1 MiB
# of SMEM takes beside the tiles' scalars, a hundredth of them: three
# quarters of it (at 1 MiB of ids Mosaic refuses the kernel)
_SMEM_PAIRS = 192 * 2 ** 10

# Tests set this to run the kernels on the CPU through the Pallas
# interpreter. Nothing else turns the interpreter on.
FORCE_PALLAS_INTERPRET = False


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return FORCE_PALLAS_INTERPRET and not _on_tpu()


def _padded(h: int) -> int:
    return -(-h // _LANES) * _LANES


def _vmem_bytes(d: int, h: int, mats: int, itemsize: int, tile: int,
                backward: bool, weight_buffers: int) -> int:
    """What a kernel holds in VMEM: the expert's matrices (`weight_buffers`
    copies), the two buffers of rows in and the one of rows out, backward
    the float32 accumulators, and a dozen [tile, block] float32
    temporaries."""
    hp = _padded(h)
    weights = mats * d * hp * itemsize * weight_buffers
    wide = (2 * d if backward else d) + _LANES
    rows = (2 * wide + d + _LANES) * tile * 4
    temps = 4 * tile * d * 4 + 12 * tile * min(hp, _H_BLOCK) * 4
    if backward:
        weights += mats * d * hp * 4
    return weights + rows + temps


def _weight_buffers(d, h, mats, itemsize, tile, backward) -> int:
    """Two buffers an expert's matrix (the next expert's is fetched under
    this one's tiles) where that fits, else one."""
    fits = _vmem_bytes(d, h, mats, itemsize, tile, backward, 2) <= _VMEM_BUDGET
    return 2 if fits else 1


def _product_dtype(dtype, interpret: bool):
    """What a product's operands are cast to. Compiled, float32 operands
    meet the MXU as one bfloat16 pass (Mosaic's default precision, and
    XLA's for the loops on the chip), so they are kept in VMEM as bfloat16:
    the same product from half the bytes. The interpreter multiplies what
    it is given, at the precision the loops' products have there."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.float32) and not interpret:
        return jnp.dtype(jnp.bfloat16)
    return dtype


def supports(d: int, h: int, gated: bool, dtype, tile: int,
             pairs: int = 0) -> bool:
    """The shapes the compiled kernels are written for: float32 or bfloat16
    activations of whole lanes, tiles of whole chunks of rows, a backward
    kernel whose accumulators and matrices fit VMEM, and `pairs` (token,
    expert) pairs routed whose sorted ids fit SMEM beside the tiles' scalars.
    The hidden width is free: a block takes its whole extent."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    mats = 3 if gated else 2
    size = _product_dtype(dtype, False).itemsize
    return (d % _LANES == 0 and tile % _CHUNK == 0 and pairs <= _SMEM_PAIRS
            and _vmem_bytes(d, h, mats, size, tile, True, 1) <= _VMEM_BUDGET)


def takes(d: int, h: int, gated: bool, dtype, tile: int,
          pairs: int = 0) -> bool:
    """Whether the kernels run this product: on a TPU for the shapes
    `supports` names; under the tests' interpreter, any float32 or bfloat16
    rows. Anything else is the loops'."""
    if _on_tpu():
        return supports(d, h, gated, dtype, tile, pairs)
    return (FORCE_PALLAS_INTERPRET and tile % _CHUNK == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)))


def _dot(a, b, ca: int, cb: int):
    """a · b contracting dim `ca` of a with dim `cb` of b, float32 result.
    Compiled, at Mosaic's own precision whatever
    `jax_default_matmul_precision` says (it refuses bf16 operands under
    "highest"); the interpreter follows the setting, as the loops do."""
    return lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=_F32,
        precision=None if _interpret() else lax.Precision.DEFAULT)


# ---------------------------------------------------------------------------
# rows
# ---------------------------------------------------------------------------

def _rows_kernel(*refs):
    *parts, o_ref = refs
    row = jnp.concatenate([p[...].astype(_F32) for p in parts], axis=1)
    o_ref[...] = row.reshape(o_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_rows(x, weight, g=None, *, interpret):
    """x [N, D], weight [N, k] (and backward the cotangent g [N, D]) ->
    float32 [N, 1, D (+ D) + 128], a row a memory tile: the token's
    activations, its cotangent, then its k combine weights on the first
    lanes of one more lane tile, so that one copy brings all a pair needs of
    its token. One pass, made here because XLA takes four to lay a row out a
    tile (PERF.md section 6, PR 42)."""
    n, d = x.shape
    wide = jnp.pad(weight.astype(_F32),
                   ((0, 0), (0, _LANES - weight.shape[1])))
    parts = [x] + ([] if g is None else [g.astype(_F32)]) + [wide]
    width = sum(p.shape[1] for p in parts)
    rows = n if n < _PACK_ROWS else _PACK_ROWS
    return kernel_call(
        "grouped_ffn_rows", _rows_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, p.shape[1]), lambda i: (i, 0))
                  for p in parts],
        out_specs=pl.BlockSpec((rows, 1, width), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, width), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
        name="grouped_ffn_rows",
    )(*parts)


class _Walk:
    """What both kernels read of the plan at a grid step, and the row copies
    of a tile: `order` the sorted pair ids, `expert` / `lo` / `rows` a
    tile's expert, first sorted position and live rows, `n` the live tiles,
    `tiles_of` the live tiles of each held expert.

    Starting a copy costs the scalar unit about 26 ns whatever the row's
    size (PERF.md section 6, PR 42), so a tile's copies are started
    `_CHUNK` rows a loop step and waited for a chunk a wait (a DMA
    semaphore counts bytes). The rows of a tile's last chunk past its last
    live row read the row of whatever pair stands at their sorted position
    and are written to a spare row past the last token."""

    def __init__(self, order, expert, lo, rows, n, tiles_of, k, tile, tokens):
        self.order, self.expert, self.lo, self.rows = order, expert, lo, rows
        self.tiles_of = tiles_of
        self.i = pl.program_id(0)
        self.n = n[0]
        self.k, self.tile, self.tokens = k, tile, tokens
        self.last_step = pl.num_programs(0) - 1
        self.row = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)

    @property
    def live(self):
        return self.row < self.rows[self.i]

    def first_of_expert(self):
        before = self.expert[jnp.maximum(self.i - 1, 0)]
        return (self.i == 0) | (before != self.expert[self.i])

    def last_of_expert(self):
        after = self.expert[jnp.minimum(self.i + 1, self.last_step)]
        return (self.i == self.n - 1) | (after != self.expert[self.i])

    def _live_chunks(self, i):
        return (self.rows[i] + _CHUNK - 1) // _CHUNK

    def _chunks(self, i, row):
        """`row(r, pair, token)` for the rows of tile i's live chunks."""
        last = self.order.shape[0] - 1
        lo = self.lo[i]

        def chunk(c, carry):
            for j in range(_CHUNK):
                r = c * _CHUNK + j
                pair = self.order[jnp.minimum(lo + r, last)]
                row(r, pair, pair // self.k)
            return carry

        lax.fori_loop(0, self._live_chunks(i), chunk, 0)

    def gather(self, i, hbm, buf, sem, slots=None):
        """Start the copies of tile i's rows of `hbm` [N, 1, W] into `buf`
        [T, 1, W]; with `slots` [T, 1, 128], note there which of its token's
        k pairs each row is."""
        def row(r, pair, token):
            if slots is not None:
                slots[r] = jnp.full((1, _LANES), pair - token * self.k,
                                    jnp.int32)
            pltpu.make_async_copy(hbm.at[pl.ds(token, 1)],
                                  buf.at[pl.ds(r, 1)], sem).start()

        self._chunks(i, row)

    def scatter(self, i, buf, hbm, sem):
        def row(r, pair, token):
            to = jnp.where(r < self.rows[i], token, self.tokens + r % _SPARE)
            pltpu.make_async_copy(buf.at[pl.ds(r, 1)], hbm.at[pl.ds(to, 1)],
                                  sem).start()

        self._chunks(i, row)

    def wait(self, i, hbm, buf, sem):
        """Wait for tile i's row copies between `hbm` and `buf`, either
        way: a wait needs the copies' size alone."""
        def chunk(c, carry):
            pltpu.make_async_copy(hbm.at[pl.ds(0, _CHUNK)],
                                  buf.at[pl.ds(0, _CHUNK)], sem).wait()
            return carry

        lax.fori_loop(0, self._live_chunks(i), chunk, 0)

    def clear(self, hbm, buf, sem):
        """Zero `hbm` [N + spare, 1, W] from the zeroed `buf` [T, 1, W], a
        tile of rows a copy (134 MB in 0.2 ms, where a broadcast of XLA's
        into this layout takes 0.45)."""
        buf[...] = jnp.zeros_like(buf)
        whole, rest = divmod(hbm.shape[0], self.tile)

        def block(j):
            return pltpu.make_async_copy(
                buf, hbm.at[pl.ds(j * self.tile, self.tile)], sem)

        def tail():
            return pltpu.make_async_copy(
                buf.at[pl.ds(0, rest)],
                hbm.at[pl.ds(whole * self.tile, rest)], sem)

        if whole:
            lax.fori_loop(0, whole, lambda j, c: (block(j).start(), c)[1], 0)
        if rest:
            tail().start()
        if whole:
            lax.fori_loop(0, whole, lambda j, c: (block(0).wait(), c)[1], 0)
        if rest:
            tail().wait()

    def ask_rows(self, hbm, buf, sems, slots):
        """The first half of `add_rows`: wait for the tile before to have
        written its rows, then ask for this tile's."""
        @pl.when(self.i > 0)
        def _():
            self.wait(self.i - 1, hbm, buf, sems.at[1])

        self.gather(self.i, hbm, buf, sems.at[0], slots)

    def add_rows(self, value, hbm, buf, sems):
        """hbm[token of row r] += value[r] for the tile's live rows:
        `ask_rows` has asked for them, here they are waited for, summed in
        the buffer and sent back."""
        self.wait(self.i, hbm, buf, sems.at[0])
        t, width = value.shape
        buf[...] = buf[...] + value.reshape(t, 1, width)
        self.scatter(self.i, buf, hbm, sems.at[1])

        @pl.when(self.i == self.n - 1)
        def _():
            self.wait(self.i, hbm, buf, sems.at[1])

    def fetch(self, hbm, buf, sems):
        """The double-buffered rows in: `buf` [2, T, 1, W]. Starts the next
        tile's copies, waits for this tile's, returns this tile's rows as
        [T, W]."""
        slot = self.i % 2

        @pl.when(self.i == 0)
        def _():
            self.gather(0, hbm, buf.at[0], sems.at[0])

        @pl.when(self.i + 1 < self.n)
        def _():
            self.gather(self.i + 1, hbm, buf.at[1 - slot], sems.at[1 - slot])

        self.wait(self.i, hbm, buf.at[slot], sems.at[slot])
        return buf[slot].reshape(self.tile, buf.shape[3])

    def pair_weight(self, rows, slots):
        """The combine weight of each row's pair [T, 1] (0 on a dead row)
        from the k weights that came with the token's row, and the mask of
        its lane among them."""
        mine = (lax.broadcasted_iota(jnp.int32, (self.tile, _LANES), 1)
                == slots[...].reshape(self.tile, _LANES)) & self.live
        weights = rows[:, rows.shape[1] - _LANES:]
        return jnp.sum(jnp.where(mine, weights, 0.0), axis=1,
                       keepdims=True), mine


def _five(items, gated, biased):
    """(w1, b1, w3, w2, b2) from the leading `items`, which hold those of
    them an expert has (no biases: None; not gated: no w3), and the rest."""
    items = list(items)
    five = tuple(items.pop(0) if present else None
                 for present in (True, biased, gated, True, biased))
    return five, items


class _In:
    """An expert's input matrix W [D, H] as the kernels hold it: as it is
    where H is whole lane tiles, else turned, [H, D] (`_turned`). The three
    products a tile makes with it, a block `hs` of H at a time."""

    def __init__(self, ref, turned):
        self.ref, self.turned = ref, turned

    def times(self, xt, hs):
        """xt · W[:, hs] -> [T, block]."""
        if self.turned:
            return _dot(xt, self.ref[0, hs, :], 1, 1)
        return _dot(xt, self.ref[0, :, hs], 1, 0)

    def back(self, d_lo, hs):
        """d_lo · W[:, hs]ᵀ -> [T, D]."""
        if self.turned:
            return _dot(d_lo, self.ref[0, hs, :], 1, 0)
        return _dot(d_lo, self.ref[0, :, hs], 1, 1)

    def add_grad(self, acc, xt, d_lo, hs):
        """acc (laid out as W is held) += xtᵀ · d_lo on the block's place."""
        if self.turned:
            acc[hs, :] += _dot(d_lo, xt, 0, 0)
        else:
            acc[:, hs] += _dot(xt, d_lo, 0, 0)


def _turned(h: int) -> bool:
    """Whether W1 and W3 are held as [H, D]: where H is not whole lane
    tiles (Nemotron's 1,856 is 14.5). A copy between VMEM and HBM moves
    whole tiles, so H goes on the sublanes, where 8 divide it; and [H, D]
    row-major is the bytes of [D, H] with D minor, the layout XLA gives such
    a parameter and its Adam moments anyway, so the turn costs no pass."""
    return h % _LANES != 0


def _h_blocks(h: int):
    """The hidden width in blocks of whole lane tiles, at most `_H_BLOCK`
    lanes each: equal ones where the width is whole tiles, else full blocks
    and what is left (1,856: three of 512 and one of 320, its last tile
    half used)."""
    block = _H_BLOCK
    if h % _LANES == 0:
        block = max(b for b in range(_LANES, min(h, _H_BLOCK) + 1, _LANES)
                    if h % b == 0)
    return [slice(s, min(s + block, h)) for s in range(0, h, block)]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, act, k, gated, biased, dtype):
    x_hbm = refs[_PREFETCHED]
    (w1_ref, b1_ref, w3_ref, w2_ref, b2_ref), rest = _five(
        refs[_PREFETCHED + 1:], gated, biased)
    y_hbm, xbuf, ybuf, slots, xsem, ysem = rest
    tile, d = ybuf.shape[0], ybuf.shape[2]
    walk = _Walk(*refs[:_PREFETCHED], k, tile, x_hbm.shape[0])
    hidden = w2_ref.shape[1]
    w1, w3 = (_In(ref, _turned(hidden)) for ref in (w1_ref, w3_ref))

    @pl.when(walk.i == 0)
    def _():
        walk.clear(y_hbm, ybuf, ysem.at[1])

    @pl.when(walk.i < walk.n)
    def _():
        walk.ask_rows(y_hbm, ybuf, ysem, slots)
        rows = walk.fetch(x_hbm, xbuf, xsem)
        xt = jnp.where(walk.live, rows[:, :d], 0.0).astype(dtype)
        o = jnp.zeros((tile, d), _F32)
        for hs in _h_blocks(hidden):
            h = w1.times(xt, hs)
            if biased:
                h = h + b1_ref[0, :, hs]
            h = act(h)
            if gated:
                h = h * w3.times(xt, hs)
            o = o + _dot(h.astype(dtype), w2_ref[0, hs, :], 1, 0)
        if biased:
            o = o + b2_ref[0]
        walk.add_rows(o * walk.pair_weight(rows, slots)[0], y_hbm, ybuf, ysem)


def _bwd_kernel(*refs, act, k, gated, biased, dtype):
    x_hbm = refs[_PREFETCHED]
    (w1_ref, b1_ref, w3_ref, w2_ref, b2_ref), rest = _five(
        refs[_PREFETCHED + 1:], gated, biased)
    dx_hbm, *rest = rest
    sums_hbm, (xbuf, dxbuf, slots, *rest) = _five(rest, gated, biased)
    (acc1, accb1, acc3, acc2, accb2), (xsem, dxsem, wsem) = _five(
        rest, gated, biased)
    tile, d = dxbuf.shape[0], dxbuf.shape[2] - _LANES
    walk = _Walk(*refs[:_PREFETCHED], k, tile, x_hbm.shape[0])
    hidden = w2_ref.shape[1]
    w1, w3 = (_In(ref, _turned(hidden)) for ref in (w1_ref, w3_ref))
    accs = [(acc, out) for acc, out in zip(
        (acc1, accb1, acc3, acc2, accb2), sums_hbm) if acc is not None]

    def write(e):
        copies = [pltpu.make_async_copy(acc, out.at[e], wsem.at[j])
                  for j, (acc, out) in enumerate(accs)]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    def clear():
        for acc, _ in accs:
            acc[...] = jnp.zeros_like(acc)

    @pl.when(walk.i == 0)
    def _():
        walk.clear(dx_hbm, dxbuf, dxsem.at[1])
        # an expert without a tile gets its zeros here
        clear()
        for e in range(walk.tiles_of.shape[0]):
            pl.when(walk.tiles_of[e] == 0)(functools.partial(write, e))

    @pl.when(walk.i < walk.n)
    def _():
        pl.when(walk.first_of_expert() & (walk.i > 0))(clear)
        walk.ask_rows(dx_hbm, dxbuf, dxsem, slots)
        rows = walk.fetch(x_hbm, xbuf, xsem)
        live = walk.live
        xt = jnp.where(live, rows[:, :d], 0.0).astype(dtype)
        g = jnp.where(live, rows[:, d:2 * d], 0.0)
        wcol, mine = walk.pair_weight(rows, slots)
        g_lo = g.astype(dtype)
        do = g * wcol                  # the unweighted output's cotangent
        do_lo = do.astype(dtype)
        dxt = jnp.zeros((tile, d), _F32)
        # the weight's gradient Σ g ∘ (h·W2 + b2), as Σ h ∘ (g·W2ᵀ) + g·b2
        dw = jnp.zeros((tile, 1), _F32)
        if biased:
            dw = jnp.sum(g * b2_ref[0], axis=1, keepdims=True)
            accb2[...] += jnp.sum(do, axis=0, keepdims=True)
        for hs in _h_blocks(hidden):
            a = w1.times(xt, hs)
            if biased:
                a = a + b1_ref[0, :, hs]
            s, act_vjp = jax.vjp(act, a)
            c = w3.times(xt, hs) if gated else None
            h = (s * c if gated else s).astype(dtype)
            gw = _dot(g_lo, w2_ref[0, hs, :], 1, 1)            # [T, block]
            dw = dw + jnp.sum(h.astype(_F32) * gw, axis=1, keepdims=True)
            dh = gw * wcol
            acc2[hs, :] += _dot(h, do_lo, 0, 0)
            (da,) = act_vjp(dh * c if gated else dh)
            da_lo = da.astype(dtype)
            w1.add_grad(acc1, xt, da_lo, hs)
            dxt = dxt + w1.back(da_lo, hs)
            if biased:
                accb1[:, hs] += jnp.sum(da, axis=0, keepdims=True)
            if gated:
                dc_lo = (dh * s).astype(dtype)
                w3.add_grad(acc3, xt, dc_lo, hs)
                dxt = dxt + w3.back(dc_lo, hs)
        # a pair's weight gradient rides its token's row of dx, on the
        # pair's own lane of one more lane tile
        walk.add_rows(
            jnp.concatenate([dxt, jnp.where(mine, dw, 0.0)], axis=1),
            dx_hbm, dxbuf, dxsem)
        pl.when(walk.last_of_expert())(
            lambda: write(walk.expert[walk.i]))


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------

def _tiles(plan, count, tile):
    """What the kernels read of the plan besides its own arrays: each tile's
    live rows (0 past the last live tile) and each held expert's live
    tiles."""
    steps = jnp.arange(plan.tile_expert.shape[0]) < plan.n_tiles
    span = jnp.where(steps, jnp.clip(plan.tile_hi - plan.tile_lo, 0, tile), 0)
    tiles_of = jnp.sum((plan.tile_expert[None] == jnp.arange(count)[:, None])
                       & steps[None], axis=1)
    return (plan.order, plan.tile_expert, plan.tile_lo, span.astype(jnp.int32),
            plan.n_tiles.reshape(1).astype(jnp.int32),
            tiles_of.astype(jnp.int32))


def _specs(d, h, buffers):
    """Block specs by operand; an index map takes the grid step and the
    prefetched arrays and names the last live tile's expert at the steps
    past it. The hidden width is a block's whole extent; where it is not
    whole lane tiles the input matrices come turned (`_turned`)."""
    def expert(shape, **mode):
        def index(i, order, e, lo, rows, n, tiles_of):
            return e[jnp.maximum(jnp.minimum(i, n[0] - 1), 0)], 0, 0

        return pl.BlockSpec((1,) + shape, index, **mode)

    mode = {} if buffers == 2 else {"pipeline_mode": pl.Buffered(buffers)}
    w_out = expert((h, d), **mode)
    return {"any": pl.BlockSpec(memory_space=pl.ANY),
            "w_in": w_out if _turned(h) else expert((d, h), **mode),
            "w_out": w_out,
            "b_in": expert((1, h)), "b_out": expert((1, d))}


def _call(label, kernel, d, dtype, w1, b1, w2, b2, plan, w3, rows, out_shape,
          scratch, *, act, k, tile, backward, interpret):
    """One of the two kernels on the plan's grid: the prefetched scalars,
    the rows [N, 1, W] of D-wide activations in HBM, the expert's matrices
    in the products' `dtype` (as `moe._loop_fwd` casts them to x's) and its
    biases as float32 lane-rows by block."""
    count, _, h = w1.shape
    gated, biased = w3 is not None, b1 is not None
    mats, size = 3 if gated else 2, dtype.itemsize
    buffers = _weight_buffers(d, h, mats, size, tile, backward)
    sp = _specs(d, h, buffers)

    def lo(a, turn=False):
        if a is None:
            return None
        a = a.astype(dtype)
        return jnp.swapaxes(a, 1, 2) if turn else a

    def lane_row(a):
        return None if a is None else a.astype(_F32)[:, None]

    turn = _turned(h)
    operands = _some(rows, lo(w1, turn), lane_row(b1), lo(w3, turn), lo(w2),
                     lane_row(b2))
    in_specs = _some(sp["any"], sp["w_in"], sp["b_in"] if biased else None,
                     sp["w_in"] if gated else None, sp["w_out"],
                     sp["b_out"] if biased else None)
    need = _vmem_bytes(d, h, mats, size, tile, backward, buffers)
    return kernel_call(
        label,
        functools.partial(kernel, act=act, k=k, gated=gated, biased=biased,
                          dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=_PREFETCHED,
            grid=(plan.tile_expert.shape[0],),
            in_specs=in_specs,
            out_specs=jax.tree_util.tree_map(lambda _: sp["any"], out_shape),
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(_VMEM_LIMIT, max(
                32 * 2 ** 20, need + 16 * 2 ** 20)))),
        interpret=interpret,
        name=label,
    )(*_tiles(plan, count, tile), *operands)


def _some(*items):
    return [a for a in items if a is not None]


# The walks are jitted so that a model's expert layers (and a remat block's
# second forward) share one trace and one lowering of each kernel, and they
# take the rows laid out, not x: a row is float32 whatever x's dtype, so
# layers whose activations differ in dtype alone (JoyAI's float32 stream and
# its bfloat16 prediction module) share them too. `interpret` is in the key
# because the tests turn the interpreter on and off.
_WALK_STATIC = ("act", "k", "tile", "d", "dtype", "interpret")


def _one_context():
    """The abstract mesh in force, set as itself. jax keys a jitted
    function's trace on its tracing context, in which a mesh never set and
    the empty mesh a remat block's second forward is traced under differ:
    under this the two are one key, and the forward's bodies are traced and
    lowered once a shape, not twice."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


@functools.partial(jax.jit, static_argnames=_WALK_STATIC)
def _walk_forward(rows, w1, b1, w2, b2, plan, w3, *, act, k, tile, d, dtype,
                  interpret):
    n = rows.shape[0]
    y = _call(
        "grouped_ffn_fwd", _fwd_kernel, d, dtype, w1, b1, w2, b2, plan, w3,
        rows, jax.ShapeDtypeStruct((n + _SPARE, 1, d), _F32),
        [pltpu.VMEM((2, tile) + rows.shape[1:], _F32),
         pltpu.VMEM((tile, 1, d), _F32),
         pltpu.VMEM((tile, 1, _LANES), jnp.int32),
         pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))],
        act=act, k=k, tile=tile, backward=False, interpret=interpret)
    return y[:n, 0]


def forward(x, weight, w1, b1, w2, b2, plan, w3, *, act, k, tile):
    """`moe._grouped_fwd`'s result: Σ over the held pairs of weight ·
    expert(x[token]), [N, D] float32."""
    interpret = _interpret()
    with _one_context():
        rows = _pack_rows(x, weight, interpret=interpret)
        return _walk_forward(rows, w1, b1, w2, b2, plan, w3, act=act, k=k,
                             tile=tile, d=x.shape[1],
                             dtype=_product_dtype(x.dtype, interpret),
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=_WALK_STATIC)
def _walk_backward(rows, w1, b1, w2, b2, plan, w3, *, act, k, tile, d, dtype,
                   interpret):
    n = rows.shape[0]
    count, _, h = w1.shape
    gated, biased = w3 is not None, b1 is not None

    def out(*shape):
        return jax.ShapeDtypeStruct(shape, _F32)

    # the matrices' sums as the kernel holds the matrices; b1's a lane-row
    # of whole lane tiles (a copy out of VMEM moves whole tiles)
    w_in = (h, d) if _turned(h) else (d, h)
    sums = _some(w_in, (1, _padded(h)) if biased else None,
                 w_in if gated else None, (h, d), (1, d) if biased else None)
    outs = _call(
        "grouped_ffn_bwd", _bwd_kernel, d, dtype, w1, b1, w2, b2, plan, w3,
        rows,
        [out(n + _SPARE, 1, d + _LANES)] + [out(count, *s) for s in sums],
        [pltpu.VMEM((2, tile) + rows.shape[1:], _F32),
         pltpu.VMEM((tile, 1, d + _LANES), _F32),
         pltpu.VMEM((tile, 1, _LANES), jnp.int32)]
        + [pltpu.VMEM(s, _F32) for s in sums]
        + [pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
           pltpu.SemaphoreType.DMA((len(sums),))],
        act=act, k=k, tile=tile, backward=True, interpret=interpret)
    dx, *outs = outs
    (dw1, db1, dw3, dw2, db2), _ = _five(outs, gated, biased)
    if _turned(h):
        dw1, dw3 = (None if a is None else jnp.swapaxes(a, 1, 2)
                    for a in (dw1, dw3))
    return (dx[:n, 0, :d], dx[:n, 0, d:d + k], dw1,
            None if db1 is None else db1[:, 0, :h], dw2,
            None if db2 is None else db2[:, 0], dw3)


def backward(x, weight, w1, b1, w2, b2, plan, w3, g, *, act, k, tile):
    """The cotangents of x (float32), weight, w1, b1, w2, b2, w3 (float32;
    None where the input is)."""
    interpret = _interpret()
    with _one_context():
        rows = _pack_rows(x, weight, g, interpret=interpret)
        return _walk_backward(rows, w1, b1, w2, b2, plan, w3, act=act, k=k,
                              tile=tile, d=x.shape[1],
                              dtype=_product_dtype(x.dtype, interpret),
                              interpret=interpret)
