"""Mixture-of-Experts: dropless sort-and-segment routing, a layer that is told
which experts it holds, and expert parallelism over a named mesh axis.

No reference analog: barrierye/Paddle has no MoE/expert-parallel machinery
(its closest sparse-capacity idea is the pserver-sharded embedding,
operators/distributed/parameter_prefetch.cc).

- **Routing** (`route`) is over ALL experts of the layer, in float32 at
  full matmul precision: softmax scores (Switch/GShard) or sigmoid scores
  with a selection-only correction bias, the chosen scores renormalised and
  scaled (DeepSeek-V3 / Nemotron-H style). Near-ties between experts flip on
  rounding, so the router never runs in the AMP dtype.
- **The experts held** are a contiguous range `(first, count)` of the
  layer's experts: the weights passed in are those experts' only. A
  (token, expert) pair whose expert is held is computed; a pair on an absent
  expert adds nothing here (on a deployment another chip adds it). Holding
  all of them is the whole layer.
- **Dispatch** sorts the held pairs by expert and cuts each expert's run
  into tiles of `TILE` rows. **The grouped product** (`_grouped_ffn`) walks
  the live tiles only, each tile one expert MLP on the [TILE, D] rows of its
  tokens, weighted and added back to them, so the work follows the pairs
  held, there is no capacity, no token is ever dropped (all tokens on one
  expert just make more tiles), and nothing of size [N, E, C] or [N*k, D]
  exists. It has two forms, chosen by the backend and the shapes alone
  (`grouped_path`; the counter `ops/grouped_ffn_lowered{path}` says which):
  on a TPU Pallas kernels (ops/pallas_kernels/grouped_ffn.py: a grid over
  the tiles, the rows copied by DMA, a weight gradient summed in VMEM over
  an expert's consecutive tiles and written once an expert); anywhere else
  `_loop_fwd` / `_loop_bwd`, loops whose trip count is the number of tiles
  the routing made, a tile's rows gathered and scatter-added by XLA, which
  are also the kernels' oracle. An expert is **plain**,
  `act(x·W1 + b1)·W2 + b2` (two matrices [D, H], [H, D]), or, where a
  third matrix `w3` [E_held, D, H] is given, **gated**,
  `(act(x·W1 + b1) ⊙ x·W3)·W2 + b2`: the same tile with one more product
  from the same rows and an elementwise product in float32 between. Its
  backward walks the same tiles again (a custom_vjp in either form: jax
  cannot reverse a loop of dynamic length), recomputing each tile's hidden
  activations (both halves of a gated expert's). The kernels' module is
  imported where the form is picked (`_kernels`), never at the top of this
  one: `import paddle_tpu` loads this module and must load no Pallas.
- **Expert parallelism** (`moe_ffn_expert_parallel`): tokens sharded over the
  axis, experts sharded over the same axis. Each device gathers the tokens
  (all-gather), computes its held experts' part for all of them, and a
  reduce-scatter sums the parts and hands each device its own tokens'
  rows. Both ride ICI; nothing is dropped, whatever the routing. Under a
  mesh without an expert axis the kernels run on each data shard's tokens
  (`moe_ffn_data_parallel`): GSPMD cannot partition a Mosaic call.
- Load-balance aux loss (Switch: E * Σ_e f_e·P_e), psum-averaged across the
  axis so it matches the unsharded run's.
"""
from __future__ import annotations

import functools
import math
import sys
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.remat import kept
from ..observability import get_registry
from ..observability.scopes import unit_scope
from .collective import shard_map

_HI = lax.Precision.HIGHEST
# rows of one expert handled per step of the grouped product. In the loops
# a tile of R rows reads its expert's matrices anew: 2·R·D·H operations for
# the 2·D·H bytes of each in bf16, R operations a byte whatever D, H and
# the number of matrices, so 256 rows read an expert's weights about as fast
# as they multiply by them on a v5e (ridge 240). The kernels fetch an
# expert's matrices once for all its consecutive tiles, so the tile no
# longer sets the products' intensity; what it sets there is the depth of a
# weight gradient's product (xᵀ·d over R rows) and the rows left empty in
# an expert's last tile, E_held * TILE / 2 a layer on average. They keep
# 256 for every shape: what a tile costs beside its products is its rows'
# copies, a cost a row and not a tile (PERF.md section 5), so a narrower
# tile wins nothing where an expert holds one tile's rows, and a wider one
# only makes last tiles emptier
TILE = 256


class Routing(NamedTuple):
    idx: jax.Array       # [N, k] int32 — chosen experts, best first
    weight: jax.Array    # [N, k] float32 — their combine weights
    aux_loss: jax.Array  # [] load-balance loss


class MoEOutput(NamedTuple):
    y: jax.Array                  # [N, D] — the held experts' part
    aux_loss: jax.Array           # []
    tokens_per_expert: jax.Array  # [E_held] int32 — pairs on each held expert
    pairs_held: jax.Array         # [] int32 — pairs on held experts, of N*k


def route(x, gate_w, k: int = 2, scoring: str = "softmax",
          correction_bias=None, norm_topk: bool = True,
          routed_scaling: float = 1.0, axis: Optional[str] = None) -> Routing:
    """Top-k router over all `gate_w.shape[1]` experts. x: [N, D]; gate_w:
    [D, E]. `scoring` "softmax": scores = softmax(x·W); "sigmoid": scores =
    sigmoid(x·W), the choice made on scores + `correction_bias` [E] (which
    carries no gradient and no weight), the weights being the plain scores.
    `norm_topk` divides the chosen scores by their sum; `routed_scaling`
    multiplies them. With `axis` (inside shard_map) the aux-loss statistics
    are averaged over the axis."""
    e = gate_w.shape[1]
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=_HI)
    logits = kept(logits, KEPT_ROUTING[0])
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"route: scoring must be 'softmax' or 'sigmoid', "
                         f"got {scoring!r}")
    choose = scores
    if correction_bias is not None:
        choose = scores + lax.stop_gradient(
            correction_bias.astype(jnp.float32))
    _, idx = lax.top_k(choose, k)                                 # [N, k]
    # named before its first reader: a reader of the unnamed value would
    # have the backward pass make the top-k again
    idx = kept(idx.astype(jnp.int32), KEPT_ROUTING[1])
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        weight = weight / jnp.maximum(
            jnp.sum(weight, axis=-1, keepdims=True), 1e-20)
    weight = weight * routed_scaling

    # Switch load-balance loss on the top-1 assignment
    top1 = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    frac_tokens = jnp.mean(top1, axis=0)                          # f_e
    share = scores / jnp.maximum(jnp.sum(scores, -1, keepdims=True), 1e-20)
    frac_probs = jnp.mean(share, axis=0)                          # P_e
    if axis is not None:
        frac_tokens = lax.pmean(frac_tokens, axis)
        frac_probs = lax.pmean(frac_probs, axis)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return Routing(idx, kept(weight, KEPT_ROUTING[2]), aux)


# ---------------------------------------------------------------------------
# dispatch: the held pairs sorted by expert, cut into tiles
# ---------------------------------------------------------------------------

class _Plan(NamedTuple):
    order: jax.Array        # [N*k] pair ids, held pairs first, by expert
    tile_expert: jax.Array  # [max_tiles] held-expert index of each tile
    tile_lo: jax.Array      # [max_tiles] first sorted position of the tile
    tile_hi: jax.Array      # [max_tiles] end of its expert's run
    n_tiles: jax.Array      # [] live tiles
    counts: jax.Array       # [E_held] pairs on each held expert


# What a remat block can keep of a layer (`core.program.keep(*KEPT)`), by the
# names `route` and `_dispatch` give it: the router's logits, the chosen
# experts and their weights, and every array of the plan that the grouped
# product's backward reads (`counts` it does not). Small integers and [N, E]
# floats, against a float32 product, a top-k, a sort and a search.
KEPT_ROUTING = ("moe/logits", "moe/idx", "moe/weight")
KEPT_PLAN = tuple(f"moe/plan.{f}" for f in _Plan._fields[:-1])
KEPT = KEPT_ROUTING + KEPT_PLAN


def _dispatch(idx, first, count: int, tile: int) -> _Plan:
    """idx: [N, k] chosen experts (of all the layer's); the held experts are
    `first .. first + count - 1` (`first` may be traced: a device's own range
    under expert parallelism)."""
    flat = idx.reshape(-1) - first
    held = (flat >= 0) & (flat < count)
    key = jnp.where(held, flat, count)          # absent experts sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    tiles = (counts + tile - 1) // tile
    tile_ends = jnp.cumsum(tiles)
    max_tiles = -(-flat.shape[0] // tile) + count
    i = jnp.arange(max_tiles, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(tile_ends, i, side="right"),
                    count - 1).astype(jnp.int32)
    within = i - (tile_ends[e] - tiles[e])
    plan = (order, e, starts[e] + within * tile, ends[e], tile_ends[-1])
    return _Plan(*map(kept, plan, KEPT_PLAN), counts)


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------

def _tile_rows(plan: _Plan, weight_flat, i, k: int, tile: int):
    """Tile i: its expert, the tokens of its rows, their combine weights
    (0 for the empty rows of an expert's last tile) and their pair ids."""
    e = plan.tile_expert[i]
    pos = plan.tile_lo[i] + jnp.arange(tile, dtype=jnp.int32)
    live = pos < plan.tile_hi[i]
    pair = plan.order.at[jnp.where(live, pos, 0)].get(
        mode="promise_in_bounds")
    wgt = jnp.where(live, weight_flat.at[pair].get(mode="promise_in_bounds"),
                    0.0)
    return e, pair // k, wgt, pair, live


def _expert_tile(xt, w1e, b1e, w2e, b2e, wgt, w3e, act):
    """One expert on one tile of rows: act(xt·W1 + b1)·W2 + b2, weighted;
    with `w3e` the hidden activation is act(xt·W1 + b1) ⊙ xt·W3 (a gated
    expert). Products take operands in xt's dtype and accumulate in float32,
    and the gate's product is of float32 halves."""
    h = jnp.dot(xt, w1e, preferred_element_type=jnp.float32)
    if b1e is not None:
        h = h + b1e
    h = act(h)
    if w3e is not None:
        h = h * jnp.dot(xt, w3e, preferred_element_type=jnp.float32)
    h = h.astype(xt.dtype)
    o = jnp.dot(h, w2e, preferred_element_type=jnp.float32)
    if b2e is not None:
        o = o + b2e
    return o * wgt[:, None]


def _at(a, e):
    return None if a is None else lax.dynamic_index_in_dim(a, e, 0, False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _grouped_ffn(x, weight, w1, b1, w2, b2, plan, w3, act, k, tile):
    """Σ over the held (token, expert) pairs of weight · expert(x[token]),
    as [N, D] float32. x: [N, D]; weight: [N, k] float32; w1: [E_held, D, H];
    w2: [E_held, H, D]; b1, b2: [E_held, H], [E_held, D] or None; w3:
    [E_held, D, H] (gated experts) or None (plain)."""
    return _grouped_fwd(x, weight, w1, b1, w2, b2, plan, w3, act, k, tile)[0]


def _lo(a, dtype):
    return None if a is None else a.astype(dtype)


_KERNELS = "paddle_tpu.ops.pallas_kernels.grouped_ffn"


def _kernels(d: int, h: int, gated: bool, dtype, tile: int, pairs: int = 0):
    """The kernels' module where they take a product of these shapes over
    `pairs` routed (token, expert) pairs, else None: on a TPU for the shapes
    they are written for, or where a test has loaded the module and turned
    its interpreter on. Imported here and not at the top, so that `import
    paddle_tpu` loads no Pallas (1.3 s of set-up for a program without a
    kernel: PERF.md section 6, PR 42)."""
    if jax.default_backend() != "tpu" and _KERNELS not in sys.modules:
        return None
    from ..ops.pallas_kernels import grouped_ffn
    taken = grouped_ffn.takes(d, h, gated, dtype, tile, pairs)
    return grouped_ffn if taken else None


def grouped_path(d: int, h: int, gated: bool, dtype, tile: int,
                 pairs: int = 0) -> str:
    """Which form the grouped product of these shapes is lowered to:
    "pallas" (ops/pallas_kernels/grouped_ffn.py: on a TPU, for the shapes
    and the count of routed pairs the kernels take) or "loop" (the loops
    over tiles below). Nothing but the backend and the shapes chooses."""
    return "pallas" if _kernels(d, h, gated, dtype, tile, pairs) else "loop"


def _shapes_of(x, w1, w3, k, tile):
    return (x.shape[1], w1.shape[2], w3 is not None, x.dtype, tile,
            x.shape[0] * k)


def _grouped_fwd(x, weight, w1, b1, w2, b2, plan, w3, act, k, tile):
    res = (x, weight, w1, b1, w2, b2, plan, w3)
    kernels = _kernels(*_shapes_of(x, w1, w3, k, tile))
    if kernels is None:
        return _loop_fwd(*res, act, k, tile)
    return kernels.forward(*res, act=act, k=k, tile=tile), res


def _grouped_bwd(act, k, tile, res, g):
    x, _, w1, _, _, _, _, w3 = res
    kernels = _kernels(*_shapes_of(x, w1, w3, k, tile))
    if kernels is None:
        return _loop_bwd(act, k, tile, res, g)
    return _cotangents(res, *kernels.backward(*res, g, act=act, k=k,
                                              tile=tile))


def _loop_fwd(x, weight, w1, b1, w2, b2, plan, w3, act, k, tile):
    """The grouped product as a loop over the live tiles: the form off the
    TPU, and the kernels' oracle."""
    res = (x, weight, w1, b1, w2, b2, plan, w3)
    w1c, w2c, w3c = w1.astype(x.dtype), w2.astype(x.dtype), _lo(w3, x.dtype)
    b1f, b2f = _lo(b1, jnp.float32), _lo(b2, jnp.float32)
    weight_flat = weight.reshape(-1)

    def body(i, y):
        e, token, wgt, _, _ = _tile_rows(plan, weight_flat, i, k, tile)
        xt = x.at[token].get(mode="promise_in_bounds")
        o = _expert_tile(xt, _at(w1c, e), _at(b1f, e), _at(w2c, e),
                         _at(b2f, e), wgt, _at(w3c, e), act)
        return y.at[token].add(o, mode="promise_in_bounds")

    y = lax.fori_loop(0, plan.n_tiles, body,
                      jnp.zeros(x.shape, jnp.float32))
    return y, res


def _loop_bwd(act, k, tile, res, g):
    x, weight, w1, b1, w2, b2, plan, w3 = res
    w1c, w2c, w3c = w1.astype(x.dtype), w2.astype(x.dtype), _lo(w3, x.dtype)
    b1f, b2f = _lo(b1, jnp.float32), _lo(b2, jnp.float32)
    weight_flat = weight.reshape(-1)
    g = g.astype(jnp.float32)

    def add_at(acc, e, d):
        if acc is None:
            return None
        return lax.dynamic_update_index_in_dim(
            acc, lax.dynamic_index_in_dim(acc, e, 0, False)
            + d.astype(jnp.float32), e, 0)

    def body(i, carry):
        dx, dwgt, dw1, db1, dw2, db2, dw3 = carry
        e, token, wgt, pair, live = _tile_rows(plan, weight_flat, i, k, tile)
        xt = x.at[token].get(mode="promise_in_bounds")
        gt = jnp.where(live[:, None],
                       g.at[token].get(mode="promise_in_bounds"), 0.0)
        args = (xt, _at(w1c, e), _at(b1f, e), _at(w2c, e), _at(b2f, e), wgt,
                _at(w3c, e))
        _, vjp = jax.vjp(functools.partial(_expert_tile, act=act), *args)
        dxt, d1, dbias1, d2, dbias2, dw, d3 = vjp(gt)
        dx = dx.at[token].add(dxt.astype(jnp.float32),
                              mode="promise_in_bounds")
        # an empty row's pair id is a live pair's: it must add nothing
        dwgt = dwgt.at[pair].add(jnp.where(live, dw, 0.0),
                                 mode="promise_in_bounds")
        return (dx, dwgt, add_at(dw1, e, d1), add_at(db1, e, dbias1),
                add_at(dw2, e, d2), add_at(db2, e, dbias2),
                add_at(dw3, e, d3))

    def zeros(a):
        return None if a is None else jnp.zeros(a.shape, jnp.float32)

    dx, dwgt, dw1, db1, dw2, db2, dw3 = lax.fori_loop(
        0, plan.n_tiles, body,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(weight_flat.shape, jnp.float32),
         zeros(w1), zeros(b1), zeros(w2), zeros(b2), zeros(w3)))

    return _cotangents(res, dx, dwgt, dw1, db1, dw2, db2, dw3)


def _cotangents(res, dx, dwgt, dw1, db1, dw2, db2, dw3):
    """The float32 sums as the cotangents of `_grouped_ffn`'s arguments."""
    x, weight, w1, b1, w2, b2, plan, w3 = res

    def like(d, a):
        return None if a is None else d.astype(a.dtype)

    plan_ct = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jax.dtypes.float0), plan)
    return (dx.astype(x.dtype), dwgt.reshape(weight.shape).astype(weight.dtype),
            like(dw1, w1), like(db1, b1), like(dw2, w2), like(db2, b2),
            plan_ct, like(dw3, w3))


_grouped_ffn.defvjp(_grouped_fwd, _grouped_bwd)


def experts_ffn(x, routing: Routing, w1, b1, w2, b2, first=0,
                act=jax.nn.gelu, tile: Optional[int] = None, w3=None):
    """The held experts' part of the layer for routed tokens x [N, D]: the
    experts `first .. first + w1.shape[0] - 1` of those `routing` chose
    among, gated where `w3` is given. Returns (y [N, D] in x's dtype, tokens
    on each held expert, pairs held)."""
    count, k = w1.shape[0], routing.idx.shape[1]
    tile = tile or TILE
    with unit_scope("dispatch"):
        plan = _dispatch(routing.idx, first, count, tile)
    get_registry().counter(
        "ops/grouped_ffn_lowered",
        path=grouped_path(*_shapes_of(x, w1, w3, k, tile))).inc()
    with unit_scope("experts"):
        y = _grouped_ffn(x, routing.weight, w1, b1, w2, b2, plan, w3, act, k,
                         tile)
    with unit_scope("combine"):
        y = y.astype(x.dtype)
    return y, plan.counts, jnp.sum(plan.counts)


def moe_ffn(x, gate_w, w1, b1, w2, b2, k: int = 2, act=jax.nn.gelu,
            experts_held: Optional[Tuple[int, int]] = None,
            scoring: str = "softmax", correction_bias=None,
            norm_topk: bool = True, routed_scaling: float = 1.0,
            w3=None) -> MoEOutput:
    """Single-device MoE FFN. x: [N, D]. gate_w: [D, E] routes over all E
    experts; w1: [E_held, D, H], b1: [E_held, H] or None, w2: [E_held, H, D],
    b2: [E_held, D] or None and, for gated experts, w3: [E_held, D, H] are
    the weights of the experts held, `experts_held = (first, count)`
    (default: all E). Pairs on experts that are not held add nothing."""
    first, count = experts_held or (0, gate_w.shape[1])
    if w1.shape[0] != count:
        raise ValueError(f"moe_ffn: {count} experts held but w1 has "
                         f"{w1.shape[0]}")
    with unit_scope("router"):
        routing = route(x, gate_w, k, scoring, correction_bias, norm_topk,
                        routed_scaling)
    y, tokens, pairs = experts_ffn(x, routing, w1, b1, w2, b2, first, act,
                                   w3=w3)
    return MoEOutput(y, routing.aux_loss, tokens, pairs)


def moe_ffn_expert_parallel(x, gate_w, w1, b1, w2, b2, mesh: Mesh,
                            axis: str = "ep", k: int = 2, act=jax.nn.gelu,
                            scoring: str = "softmax", correction_bias=None,
                            norm_topk: bool = True,
                            routed_scaling: float = 1.0,
                            w3=None) -> MoEOutput:
    """Expert-parallel MoE FFN over `axis`: x [N, D] sharded on tokens, the
    E experts' weights (w1, w2, the biases and a gated layer's w3) sharded
    on the expert dim (E / ep held a device).
    Every device routes its own tokens, the tokens and their routing are
    all-gathered, each device computes its experts' part for all tokens and
    a reduce-scatter sums the parts back to the tokens' owners. Equal to
    `moe_ffn` over the whole batch, whatever the routing."""
    ep = mesh.shape[axis]
    e = gate_w.shape[1]
    if e % ep != 0:
        raise ValueError(f"num experts {e} not divisible by mesh axis {ep}")
    held = e // ep
    has_bias = b1 is not None

    def local(xs, gw, cb, w1s, w2s, *rest):
        b1s, b2s = rest[:2] if has_bias else (None, None)
        w3s = rest[-1] if w3 is not None else None
        with unit_scope("router"):
            r = route(xs, gw, k, scoring, cb, norm_topk, routed_scaling,
                      axis=axis)
        x_all, idx, weight = (lax.all_gather(a, axis, axis=0, tiled=True)
                              for a in (xs, r.idx, r.weight))
        first = lax.axis_index(axis) * held
        y, tokens, pairs = experts_ffn(
            x_all, Routing(idx, weight, r.aux_loss), w1s, b1s, w2s, b2s,
            first, act, w3=w3s)
        y = lax.psum_scatter(y, axis, scatter_dimension=0, tiled=True)
        return (y, r.aux_loss, lax.all_gather(tokens, axis, axis=0, tiled=True),
                lax.psum(pairs, axis))

    cb = (jnp.zeros((e,), jnp.float32) if correction_bias is None
          else correction_bias)
    held_only = ((b1, b2) if has_bias else ()) + (
        (w3,) if w3 is not None else ())
    args = (x, gate_w, cb, w1, w2) + held_only
    specs = (P(axis), P(), P()) + (P(axis),) * (2 + len(held_only))
    f = shard_map(local, mesh, in_specs=specs,
                  out_specs=(P(axis), P(), P(), P()))
    return MoEOutput(*f(*args))


def moe_ffn_data_parallel(x, gate_w, w1, b1, w2, b2, mesh: Mesh,
                          axis: Optional[str], k: int = 2, act=jax.nn.gelu,
                          experts_held: Optional[Tuple[int, int]] = None,
                          scoring: str = "softmax", correction_bias=None,
                          norm_topk: bool = True, routed_scaling: float = 1.0,
                          w3=None) -> MoEOutput:
    """`moe_ffn` under a mesh without an expert axis, for the form GSPMD
    cannot partition (the Mosaic kernels): the tokens split over `axis`
    (the mesh's data axis; None: every device computes them all), the
    weights whole on every device, each shard's tokens through the held
    experts inside a shard_map. The load-balance statistics are averaged and
    the counts summed over the axis, so the result is `moe_ffn`'s."""
    first = experts_held[0] if experts_held else 0
    if axis is not None and x.shape[0] % mesh.shape[axis]:
        axis = None
    given = [a is not None for a in (correction_bias, b1, b2, w3)]

    def local(xs, gw, w1s, w2s, *rest):
        rest = iter(rest)
        cb, b1s, b2s, w3s = (next(rest) if g else None for g in given)
        with unit_scope("router"):
            r = route(xs, gw, k, scoring, cb, norm_topk, routed_scaling,
                      axis=axis)
        y, tokens, pairs = experts_ffn(xs, r, w1s, b1s, w2s, b2s, first, act,
                                       w3=w3s)
        if axis is not None:
            tokens, pairs = lax.psum(tokens, axis), lax.psum(pairs, axis)
        return y, r.aux_loss, tokens, pairs

    whole = [a for a in (correction_bias, b1, b2, w3) if a is not None]
    f = shard_map(local, mesh,
                  in_specs=(P(axis),) + (P(),) * (3 + len(whole)),
                  out_specs=(P(axis), P(), P(), P()))
    return MoEOutput(*f(x, gate_w, w1, w2, *whole))


def init_moe_params(rng, d_model: int, d_hidden: int, num_experts: int,
                    dtype=jnp.float32, gated: bool = False):
    """Convenience initializer returning (gate_w, w1, b1, w2, b2), and with
    `gated` a sixth, w3 (drawn like w1)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_hidden)

    def first(key):
        return jax.random.normal(
            key, (num_experts, d_model, d_hidden), dtype) * s1

    return (
        jax.random.normal(k1, (d_model, num_experts), dtype) * s1,
        first(k2),
        jnp.zeros((num_experts, d_hidden), dtype),
        jax.random.normal(k3, (num_experts, d_hidden, d_model), dtype) * s2,
        jnp.zeros((num_experts, d_model), dtype),
    ) + ((first(jax.random.fold_in(k2, 3)),) if gated else ())
