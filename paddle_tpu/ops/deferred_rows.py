"""Deferred row updates — O(touched-rows) sparse embedding optimization.

Reference analog: the SelectedRows sparse-apply path
(``paddle/fluid/operators/optimizers/sgd_op.cc`` SelectedRows branch,
``adagrad_op.cc`` SparseAdagradFunctor merge+row-update, ``adam_op.cc``
SparseAdamFunctor lazy_mode, ``math/selected_rows_functor.cc`` MergeAdd)
whose cost is O(touched rows), and the Downpour sparse-table row layout
that stores the accumulator next to the embedding in the same row
(g2sum in pslib's DownpourSparseTable — here the optional "state columns"
of the table). XLA has no in-place row scatter: ``table.at[ids].add(rows)``
lowers to a full read+write pass over the table (measured ~10.9 ms per
[33.5M,16] f32 table on v5e regardless of how few rows are touched), so a
literal translation pays O(table) per step — a cost-model regression vs
the reference.

TPU-native redesign, built from measured v5e access costs (random row
gathers ~10-30 ns/row; element gathers/scatters into sub-GB arrays
~5-13 ns; per-row DMA scatter impossible — Mosaic requires 128-lane
aligned slices; binary search dead — 17 rounds x 1.7M scalar gathers
measured 208 ms):

- a position table ``postab [V] int32`` maps id -> index of its LATEST
  pending entry (-1 = none): the pending "join" is ONE element gather.
- an append-only log of pending entries: ``log_ids [C]``,
  ``log_raw [C, Dt]`` (per-step deltas, folded into the table later) and
  ``log_cum [C, Dt]`` (cumulative delta since the last fold, what readers
  add to the base row). A re-touched id appends a NEW entry whose cum
  includes the old one; postab moves to it; the shadowed entry stays and
  is still correct for the fold (raw deltas add).
- every lookup returns ``base[ids] + log_cum[postab[ids]]`` — the exact
  serial-update value regardless of fold cadence. The fold (its own
  program, run by the executor epilogue every K steps) scatter-adds all
  raw deltas into the table in ONE amortized O(table) pass, clears
  postab, and resets the log.
- the deferred optimizer op performs NO large random access at all: the
  lookup op additionally outputs its gathered current rows and cum rows,
  and the optimizer reuses them through the step's unique-merge
  permutation (all small-array ops), computing deltas against exact
  current values — which makes the scheme EXACT (not stale) for SGD,
  Adagrad, and lazy Adam; deltas compose additively by construction.
- optimizer moment state lives in extra columns of the same table row
  ("state columns", the Downpour g2sum layout): one gather, one log, one
  fold pass serve param and moments together. The model slices the
  visible columns ``[:vis]`` after the lookup.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..core.registry import register_op
from ..core.selected_rows import SelectedRows
from ..observability.scopes import unit_scope

SENTINEL = 2**31 - 1


# ---------------------------------------------------------------------------
# forward join (used by the lookup_table kernel)
# ---------------------------------------------------------------------------

def lookup_join(postab, log_cum, base_rows, q):
    """Current rows for query ids: base gather + postab-indexed cum rows.

    postab: [V] int32; log_cum: [C, Lw] (row width padded to a 128-lane
    multiple — lane-aligned rows gather ~5x faster than the narrow
    column-major layout XLA must use for the un-paddable base table);
    base_rows: [Q, Dt] (= table[q]); q: [Q] int32.
    Returns (cur_rows [Q, Dt], cum_rows [Q, Dt]).
    """
    dt = base_rows.shape[-1]
    lw = log_cum.shape[-1]
    pos = postab[q]                                     # [Q] element gather
    hit = (pos >= 0)[:, None]
    cum_full = jnp.take(log_cum, pos.clip(0), axis=0)   # [Q, Lw] row gather
    if lw > dt:
        # narrow via a 0/1 projection dot (exact in f32): a plain slice
        # gets fused INTO the gather as slice_sizes=(1,dt), which XLA
        # lowers as a serial while loop (measured 187 ms); full-row
        # gathers vectorize (measured ~1 ms)
        proj = jnp.eye(lw, dt, dtype=log_cum.dtype)
        cum = jax.lax.dot_general(
            cum_full, proj, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
    else:
        cum = cum_full
    cum = jnp.where(hit, cum, 0)
    return base_rows + cum.astype(base_rows.dtype), cum


# ---------------------------------------------------------------------------
# unique-merge (MergeAdd parity, selected_rows_functor.cc)
# ---------------------------------------------------------------------------

def uniq_merge(ids, rows, r):
    """Combine duplicate ids; also return a representative original
    position per unique id (for reusing forward-gathered rows).

    ids [Q], rows [Q, D] -> (uids [r] ascending + SENTINEL pads,
    utot [r, D] summed rows, rep [r] original index of one occurrence).
    r >= Q required (static capacity, checked at trace time).
    """
    qn = ids.shape[0]
    d = rows.shape[-1]
    if qn > r:
        raise ValueError(
            f"deferred rows_per_step={r} is smaller than this step's "
            f"{qn} lookup rows — raise rows_per_step (static capacity)")
    if qn == 0:
        # the segment machinery below needs >= 1 element (`first` would be
        # [1] against 0 rows); an empty batch is all pads by definition
        return (jnp.full((r,), SENTINEL, jnp.int32),
                jnp.zeros((r, d), rows.dtype),
                jnp.zeros((r,), jnp.int32))
    # the sort and the merge are a part of the row update with a name of
    # its own in the compiled step (`rows/merge` under the model's `rows`)
    with unit_scope("merge"):
        order = jnp.argsort(ids)
        sids = ids[order]
        srows = rows[order]
        first = jnp.concatenate([jnp.ones((1,), bool), sids[1:] != sids[:-1]])
        seg = (jnp.cumsum(first) - 1).astype(jnp.int32)
        nu = seg[-1] + 1
        utot = jnp.zeros((qn, d), srows.dtype).at[seg].add(srows)
        rep = jnp.full((qn,), 0, jnp.int32).at[seg].max(
            order.astype(jnp.int32))
        # unique ids via the representative positions — an O(r) element
        # gather from the small id array instead of a second O(r) scatter
        uids = jnp.where(jnp.arange(qn) < nu, ids[rep], SENTINEL)
    if qn < r:
        uids = jnp.concatenate([uids, jnp.full((r - qn,), SENTINEL, jnp.int32)])
        utot = jnp.concatenate([utot, jnp.zeros((r - qn, d), utot.dtype)])
        rep = jnp.concatenate([rep, jnp.zeros((r - qn,), jnp.int32)])
    return uids, utot, rep


def _grad_rows(g):
    if not isinstance(g, SelectedRows):
        raise TypeError(
            "deferred-row optimizer ops need a SelectedRows gradient "
            "(embedding built with is_sparse=True); got a dense array")
    return g.ids.astype(jnp.int32), g.rows


# ---------------------------------------------------------------------------
# shared optimizer-op machinery
# ---------------------------------------------------------------------------

def _deferred_common(inputs, attrs):
    """Returns (uids [R], utot [R,vis], cur_u [R,Dt], cum_u [R,Dt],
    valid [R,1], plus the log/postab state) — zero large random accesses:
    current and cum rows come from the lookup's outputs via the
    unique-merge permutation."""
    (g,) = inputs["Grad"]
    (fwd_rows,) = inputs["FwdRows"]
    (fwd_cum,) = inputs["FwdCum"]
    (postab,) = inputs["PendingPos"]
    (log_ids,) = inputs["LogIds"]
    (count,) = inputs["Count"]
    r = int(attrs["rows_per_step"])
    vis = int(attrs["vis"])
    dt = fwd_rows.shape[-1]
    ids, grows = _grad_rows(g)
    if grows.shape[-1] not in (vis, dt):
        raise ValueError(
            f"deferred op: grad rows have {grows.shape[-1]} cols, "
            f"expected vis={vis} (or padded {dt})")
    (log_raw,) = inputs["LogRaw"]
    cdt = log_raw.dtype  # compute dtype follows the table/log precision
    uids, utot, rep = uniq_merge(ids, grows[:, :vis].astype(cdt), r)
    flat_rows = fwd_rows.reshape(-1, dt)
    flat_cum = fwd_cum.reshape(-1, dt)
    if flat_rows.shape[0] != ids.shape[0]:
        raise ValueError(
            f"deferred op: FwdRows carries {flat_rows.shape[0]} rows but "
            f"the gradient has {ids.shape[0]} — the rewrite requires the "
            f"single lookup site's output")
    cur_u = flat_rows[rep].astype(cdt)                  # [R, Dt] small gather
    cum_u = flat_cum[rep].astype(cdt)
    valid = (uids != SENTINEL)[:, None]
    return (uids, utot, rep, cur_u, cum_u, valid,
            postab, log_ids, count, r, vis, dt)


def _append(inputs, outputs_extra, postab, log_ids, count, uids, raw_new,
            cum_new, valid):
    """Append the step's entries at [count, count+R) and repoint postab.
    Contract: the fold epilogue runs before the log wraps (the optimizer
    attaches it at cadence C/R); entries are never overwritten live."""
    (log_raw,) = inputs["LogRaw"]
    (log_cum,) = inputs["LogCum"]
    c = count.reshape(()).astype(jnp.int32)
    z = jnp.zeros((), jnp.int32)
    r, dt = raw_new.shape
    lw = log_raw.shape[-1]
    raw_new = jnp.where(valid, raw_new, 0).astype(log_raw.dtype)
    cum_new = jnp.where(valid, cum_new, 0).astype(log_cum.dtype)
    if lw > dt:  # lane-padded log rows (see lookup_join)
        pad = jnp.zeros((r, lw - dt), log_raw.dtype)
        raw_new = jnp.concatenate([raw_new, pad], axis=-1)
        cum_new = jnp.concatenate([cum_new, pad], axis=-1)
    out = {
        "LogIdsOut": [lax.dynamic_update_slice(log_ids, uids, (c,))],
        "LogRawOut": [lax.dynamic_update_slice(log_raw, raw_new, (c, z))],
        "LogCumOut": [lax.dynamic_update_slice(log_cum, cum_new, (c, z))],
        "PendingPosOut": [postab.at[uids].set(
            c + jnp.arange(r, dtype=jnp.int32), mode="drop")],
        "CountOut": [count + r],
    }
    out.update(outputs_extra)
    return out


def _lr(inputs):
    (lr,) = inputs["LearningRate"]
    return lr.reshape(())


# ---------------------------------------------------------------------------
# optimizer ops
# ---------------------------------------------------------------------------

@register_op("sgd_row_deferred", differentiable=False)
def _sgd_row_deferred(ctx, inputs, attrs):
    """sgd_op.cc SelectedRows branch, deferred: delta = -lr * merged_g."""
    (uids, utot, rep, cur_u, cum_u, valid, postab, log_ids, count,
     r, vis, dt) = _deferred_common(inputs, attrs)
    delta = -_lr(inputs) * utot
    return _append(inputs, {}, postab, log_ids, count, uids,
                   delta, cum_u + delta, valid)


@register_op("adagrad_row_deferred", differentiable=False)
def _adagrad_row_deferred(ctx, inputs, attrs):
    """adagrad_op.cc SparseAdagradFunctor, deferred: G rides in state
    columns [vis:2vis] of the row (Downpour g2sum layout); touched rows
    advance G += g^2 and p -= lr*g/(sqrt(G)+eps) against exact current
    values."""
    (uids, utot, rep, cur_u, cum_u, valid, postab, log_ids, count,
     r, vis, dt) = _deferred_common(inputs, attrs)
    if dt != 2 * vis:
        raise ValueError(
            f"adagrad_row_deferred: table row has {dt} cols, expected "
            f"2*vis={2*vis} (param | accumulator state columns)")
    eps = attrs.get("epsilon", 1e-6)
    g_now = cur_u[:, vis:]
    g_delta = utot * utot
    g_new = g_now + g_delta
    p_delta = -_lr(inputs) * utot / (jnp.sqrt(g_new) + eps)
    raw = jnp.concatenate([p_delta, g_delta], axis=-1)
    return _append(inputs, {}, postab, log_ids, count, uids,
                   raw, cum_u + raw, valid)


@register_op("adam_row_deferred", differentiable=False)
def _adam_row_deferred(ctx, inputs, attrs):
    """adam_op.cc SparseAdamFunctor lazy_mode, deferred: m/v ride in state
    columns [vis:2vis] / [2vis:3vis]; only touched rows advance m/v (the
    reference's lazy semantics); beta powers advance every step as
    scalars."""
    (uids, utot, rep, cur_u, cum_u, valid, postab, log_ids, count,
     r, vis, dt) = _deferred_common(inputs, attrs)
    if dt != 3 * vis:
        raise ValueError(
            f"adam_row_deferred: table row has {dt} cols, expected "
            f"3*vis={3*vis} (param | moment1 | moment2 state columns)")
    (b1p,) = inputs["Beta1Pow"]
    (b2p,) = inputs["Beta2Pow"]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr_t = _lr(inputs) * jnp.sqrt(1 - b2p.reshape(())) / (1 - b1p.reshape(()))
    m_now = cur_u[:, vis:2 * vis]
    v_now = cur_u[:, 2 * vis:]
    m_new = b1 * m_now + (1 - b1) * utot
    v_new = b2 * v_now + (1 - b2) * utot * utot
    p_delta = -lr_t * m_new / (jnp.sqrt(v_new) + eps)
    raw = jnp.concatenate([p_delta, m_new - m_now, v_new - v_now], axis=-1)
    return _append(inputs, {"Beta1PowOut": [b1p * b1],
                            "Beta2PowOut": [b2p * b2]},
                   postab, log_ids, count, uids, raw, cum_u + raw, valid)


# ---------------------------------------------------------------------------
# fold
# ---------------------------------------------------------------------------

@register_op("deferred_fold", differentiable=False)
def _deferred_fold(ctx, inputs, attrs):
    """Fold all pending raw deltas into the table: ONE O(table) streaming
    scatter pass, amortized over K steps by the executor epilogue cadence.
    Shadowed (superseded) entries are safe — raw deltas add; sentinel ids
    are out of bounds and dropped. Clears postab and resets the log.
    Semantically a pure representation change: reads are exact before and
    after (base+cum == base')."""
    (p,) = inputs["Param"]
    (log_ids,) = inputs["LogIds"]
    (log_raw,) = inputs["LogRaw"]
    (log_cum,) = inputs["LogCum"]
    (postab,) = inputs["PendingPos"]
    (count,) = inputs["Count"]
    dt = p.shape[-1]
    return {
        "ParamOut": [p.at[log_ids].add(
            log_raw[:, :dt].astype(p.dtype), mode="drop")],
        "PendingPosOut": [jnp.full_like(postab, -1)],
        "LogIdsOut": [jnp.full_like(log_ids, SENTINEL)],
        # stale log rows are unreachable once log_ids is sentinel and
        # postab is cleared — pass them through instead of zeroing 1.7GB
        "LogRawOut": [log_raw],
        "LogCumOut": [log_cum],
        "CountOut": [jnp.zeros_like(count)],
    }


@register_op("deferred_init_state_cols", differentiable=False)
def _deferred_init_state_cols(ctx, inputs, attrs):
    """Startup-time init of a table's state columns (Downpour g2sum layout):
    keep the visible [:vis] initializer output, fill [vis:] with the
    moment initial value (adagrad initial_accumulator_value / adam 0)."""
    (p,) = inputs["Param"]
    vis = int(attrs["vis"])
    val = attrs.get("value", 0.0)
    state = jnp.full((p.shape[0], p.shape[1] - vis), val, p.dtype)
    return {"ParamOut": [jnp.concatenate([p[:, :vis], state], axis=-1)]}


# ---------------------------------------------------------------------------
# packed row-major tables — direct O(touched-rows) updates
# ---------------------------------------------------------------------------
#
# The deferred log above amortizes the scatter *pass*, but measurement shows
# XLA's scatter into the narrow table costs ~6.4 ns per touched ELEMENT
# regardless of batching (the [V,D] f32 table is forced into a column-major
# {0,1} layout because a row-major tile would pad D -> 128 and 8x the
# memory; every row update then writes D scattered lines). The fix is to
# make the rows physically contiguous WITHOUT the f32 padding blowup:
# bit-split each f32 into two u16 lanes and store the table as
# [V, 128] uint16 ({1,0}, lane-aligned, zero padding waste for up to 64
# packed f32 values — param + moment state columns in one row, the same
# Downpour row layout). Measured on v5e: full-row gathers 1.07 ms and
# scatter-SET row updates 7.4 ms per 106k rows, vs 4.6 ms / ~23 ms on the
# column-major f32 table — so each step can simply gather, compute the
# exact optimizer update, and scatter the new rows back: serial-exact
# semantics with no pending state at all.

PACK_LANES = 128  # u16 lanes per packed row (64 f32 values max)


def pack_rows(x, lanes=PACK_LANES):
    """[N, D] f32 -> [N, lanes] uint16 (bit-exact; zero-padded)."""
    n, d = x.shape
    u = lax.bitcast_convert_type(x, jnp.uint16).reshape(n, 2 * d)
    if 2 * d > lanes:
        raise ValueError(f"pack_rows: {d} f32 values need {2*d} u16 lanes "
                         f"> {lanes}")
    if 2 * d < lanes:
        u = jnp.concatenate(
            [u, jnp.zeros((n, lanes - 2 * d), jnp.uint16)], axis=-1)
    return u


def unpack_rows(u, d):
    """[N, lanes] uint16 -> [N, d] f32 (bit-exact)."""
    n = u.shape[0]
    return lax.bitcast_convert_type(
        u[:, :2 * d].reshape(n, d, 2), jnp.float32)


def _row_major(chunk):
    """Pin a [rows, lanes] chunk cut from (or bound for) a packed table to
    the table's row-major layout, so that only the chunk is relaid out. The
    u16<->f32 bitcast prefers the transposed layout, and through
    dynamic_slice / dynamic_update_slice XLA gives that layout to the whole
    loop-carried table. On the v5e without this pin (PR 21 chip run) the
    AUTO-layout startup step handed the 33.5M-row table out transposed
    (major_to_minor=(1, 0)), its rows read back NaN and every DeepFM loss
    was NaN, with no error anywhere. Compiled ahead of time the layout
    cannot be afforded either way: a default-layout startup ends in an
    8 GiB transposing copy, and a train step handed the transposed table
    begins with one (RESOURCE_EXHAUSTED, 16.01G of 15.75G hbm)."""
    return with_layout_constraint(chunk, Layout(major_to_minor=(0, 1)))


@register_op("rowpack_init", differentiable=False)
def _rowpack_init(ctx, inputs, attrs):
    """Initialize a packed table: visible columns ~ U(low, high), state
    columns = state_value, packed to [V, lanes] uint16.

    Assembled in row chunks with an in-place fori/DUS loop — generating
    the whole table in f32 first would transiently need ~2.5x the packed
    size (OOM at Criteo scale). The final chunk's DUS start is clamped, so
    a remainder chunk re-draws some earlier rows — fine for random init."""
    v = int(attrs["height"])
    vis = int(attrs["vis"])
    dt = int(attrs["dt"])
    low, high = attrs.get("low", -0.1), attrs.get("high", 0.1)
    sv = attrs.get("state_value", 0.0)
    cs = min(v, 1 << 20)
    n_chunks = -(-v // cs)
    key = ctx.rng()

    def chunk(i):
        visv = jax.random.uniform(
            jax.random.fold_in(key, i), (cs, vis), jnp.float32, low, high)
        rows = (jnp.concatenate(
            [visv, jnp.full((cs, dt - vis), sv, jnp.float32)], axis=-1)
            if dt > vis else visv)
        return pack_rows(rows)

    out = jnp.zeros((v, PACK_LANES), jnp.uint16)

    def body(i, acc):
        start = jnp.minimum(i * cs, v - cs).astype(jnp.int32)
        return lax.dynamic_update_slice(
            acc, _row_major(chunk(i)), (start, jnp.zeros((), jnp.int32)))

    return {"Out": [lax.fori_loop(0, n_chunks, body, out)]}


@register_op("rowpack_init_state_cols", differentiable=False)
def _rowpack_init_state_cols(ctx, inputs, attrs):
    """Startup-time re-init of a PACKED table's state columns: unpack each
    row chunk, overwrite cols [vis:dt] with the optimizer's initial value
    (adagrad initial_accumulator_value / adam 0), repack. Emitted by the
    packed-rows optimizer setup so state columns are well-defined no
    matter what the table initializer wrote there (a uniform init in the
    G columns would make adagrad take sqrt of a negative sum)."""
    (p,) = inputs["Param"]
    vis = int(attrs["vis"])
    dt = int(attrs["dt"])
    val = attrs.get("value", 0.0)
    v = p.shape[0]
    cs = min(v, 1 << 20)
    n_chunks = -(-v // cs)

    def body(i, acc):
        start = jnp.minimum(i * cs, v - cs).astype(jnp.int32)
        z = jnp.zeros((), jnp.int32)
        chunk = _row_major(
            lax.dynamic_slice(acc, (start, z), (cs, acc.shape[1])))
        rows = unpack_rows(chunk, dt)
        rows = jnp.concatenate(
            [rows[:, :vis], jnp.full((cs, dt - vis), val, jnp.float32)],
            axis=-1)
        return lax.dynamic_update_slice(
            acc, _row_major(pack_rows(rows)), (start, z))

    return {"ParamOut": [lax.fori_loop(0, n_chunks, body, p)]}


def _packed_common(inputs, attrs):
    """uniq-merge the SelectedRows grad and pull current rows out of the
    lookup's forward output (no additional large gathers)."""
    (g,) = inputs["Grad"]
    (fwd_rows,) = inputs["FwdRows"]
    r = int(attrs["rows_per_step"])
    vis = int(attrs["vis"])
    dt = fwd_rows.shape[-1]
    ids, grows = _grad_rows(g)
    uids, utot, rep = uniq_merge(ids, grows[:, :vis].astype(jnp.float32), r)
    cur_u = fwd_rows.reshape(-1, dt)[rep].astype(jnp.float32)
    valid = (uids != SENTINEL)[:, None]
    return uids, utot, cur_u, valid, vis, dt


def _packed_write(p, uids, new_rows):
    return p.at[uids].set(pack_rows(new_rows), mode="drop",
                          unique_indices=True)


@register_op("sgd_row_packed", differentiable=False)
def _sgd_row_packed(ctx, inputs, attrs):
    """sgd_op.cc SelectedRows branch on a packed table: touched rows get
    p -= lr * merged_g, written back as one row-major scatter-set."""
    (p,) = inputs["Param"]
    uids, utot, cur_u, valid, vis, dt = _packed_common(inputs, attrs)
    new = jnp.where(valid, cur_u[:, :vis] - _lr(inputs) * utot, cur_u[:, :vis])
    return {"ParamOut": [_packed_write(p, uids, new)]}


@register_op("adagrad_row_packed", differentiable=False)
def _adagrad_row_packed(ctx, inputs, attrs):
    """adagrad_op.cc SparseAdagradFunctor on a packed table: G rides in
    the state columns; touched rows advance G += g^2,
    p -= lr*g/(sqrt(G)+eps); one gather (forward, reused) + one
    scatter-set per step."""
    (p,) = inputs["Param"]
    eps = attrs.get("epsilon", 1e-6)
    uids, utot, cur_u, valid, vis, dt = _packed_common(inputs, attrs)
    g_new = cur_u[:, vis:2 * vis] + utot * utot
    p_new = cur_u[:, :vis] - _lr(inputs) * utot / (jnp.sqrt(g_new) + eps)
    rows = jnp.where(valid, jnp.concatenate([p_new, g_new], axis=-1),
                     cur_u[:, :2 * vis])
    return {"ParamOut": [_packed_write(p, uids, rows)]}


@register_op("adam_row_packed", differentiable=False)
def _adam_row_packed(ctx, inputs, attrs):
    """adam_op.cc SparseAdamFunctor lazy_mode on a packed table: m/v ride
    in state columns; beta powers advance per step as scalars."""
    (p,) = inputs["Param"]
    uids, utot, cur_u, valid, vis, dt = _packed_common(inputs, attrs)
    (b1p,) = inputs["Beta1Pow"]
    (b2p,) = inputs["Beta2Pow"]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr_t = _lr(inputs) * jnp.sqrt(1 - b2p.reshape(())) / (1 - b1p.reshape(()))
    m_new = b1 * cur_u[:, vis:2 * vis] + (1 - b1) * utot
    v_new = b2 * cur_u[:, 2 * vis:3 * vis] + (1 - b2) * utot * utot
    p_new = cur_u[:, :vis] - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    rows = jnp.where(valid, jnp.concatenate([p_new, m_new, v_new], axis=-1),
                     cur_u[:, :3 * vis])
    return {"ParamOut": [_packed_write(p, uids, rows)],
            "Beta1PowOut": [b1p * b1], "Beta2PowOut": [b2p * b2]}
