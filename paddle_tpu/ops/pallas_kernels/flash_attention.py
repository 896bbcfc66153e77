"""FlashAttention for TPU: online-softmax attention without the T×T tensor.

Replaces the reference's attention pattern (matmul → softmax → dropout →
matmul over a materialized [B,H,T,T] score tensor — PaddleNLP on the SURVEY
§2.1 op set) with a memory-bandwidth-shaped design:

- forward: a Pallas kernel tiles Q into VMEM blocks and streams K/V blocks
  through the MXU, keeping the running max/denominator in VMEM scratch —
  HBM traffic is O(T·D) instead of O(T²); attention-probability dropout is
  generated *inside* the kernel from the on-core PRNG (per-block reseed),
  so no mask tensor ever touches HBM;
- backward: ONE Pallas kernel recomputes p from the saved (q, k, lse)
  blockwise, over a k block's q blocks on transposed scores: a tile's
  scores and their cotangent are made once and feed dv, dk (accumulated
  over the k block's run) and dq (accumulated for the whole head in VMEM) —
  nothing quadratic is stored between fwd and bwd. A call with a per-q bias,
  or whose head is too long for that accumulator, runs a dq kernel (over a
  q block's k blocks) beside the dk/dv kernel instead. Dropout masks are
  regenerated bit-identically from the same per-(batch, q-block, k-block)
  seeds;
- the blocked kernels (more than one block a side) walk a list of visited
  tiles, under `causal` the triangle only ("The blocked kernels' tile
  schedule" below);
- a pure-JAX two-pass fallback with identical semantics runs on CPU (tests)
  and for shapes the kernel doesn't tile.

The public entry is `flash_attention(q, k, v, bias, causal, ...)` wrapped in
`jax.custom_vjp`, so the framework's per-op autodiff tape picks up the
memory-efficient backward automatically.

Bias is additive, broadcastable against [B, H, Tq, Tk] — the BERT input mask
([B,1,1,T]) and ALiBi-style biases both fit, and the bias gradient is
returned (reduced over broadcast dims).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.remat import kept as _kept
from ._account import kernel_call

# 512² blocks keep the whole [T,T] score tile in VMEM for BERT-scale
# sequence lengths: measured on v5e, bq=bk=512 runs the forward ~2.5× faster
# than 128² (fewer grid steps amortize the per-step DMA + online-softmax
# corrections). Long sequences take 1,024²: `_pick_dense_blocks`.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_LANES = 128  # TPU lane width: scratch stats are kept lane-replicated
_NEG_INF = -1e30

# Tests set this to run the Pallas kernels on CPU through the interpreter
# (dropout kernels need pltpu.InterpretParams; the interpreter's PRNG
# returns zeros, so dropout-path numerics are TPU-only). Nothing else turns
# the interpreter on.
FORCE_PALLAS_INTERPRET = False

# The residuals a forward rule makes itself, by the names a remat block keeps
# them under (`core.program.keep(*KEPT)`). Both or neither: the backward
# kernels read both, and with one of them missing the forward call is made
# again whole.
KEPT = ("flash_attention/out", "flash_attention/lse")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# In-kernel dropout: per-(b, q-block, k-block) reseed of the core PRNG, so
# forward and both backward kernels regenerate identical masks regardless of
# their grid iteration order.
# ---------------------------------------------------------------------------

def _keep_mask(seed_ref, block_index, shape, rate, transposed=False):
    # Mosaic supports at most 2 prng_seed values — the caller folds
    # (b, q-block, k-block) into one grid-order-independent index so the
    # same logical block regenerates the same stream in all three kernels.
    # `transposed` gives the same draw of `shape` as its transpose (the
    # dk/dv kernel's scores are [bk, bq]).
    pltpu.prng_seed(seed_ref[0], block_index)
    bits = pltpu.prng_random_bits(shape)
    if transposed:
        bits = bits.T
    bits = lax.bitcast_convert_type(bits, jnp.uint32)
    # drop iff bits < rate·2³² → P(keep) = 1 − rate
    return bits >= jnp.uint32(int(round(rate * 4294967296.0)) & 0xFFFFFFFF)


def _block_index(b, iq, ik, nq, nk):
    return (b * nq + iq) * nk + ik


def _seed_from_key(dropout_key):
    if dropout_key is None:
        return jnp.zeros((1,), jnp.int32)
    return jax.random.randint(dropout_key, (1,), 0, np.iinfo(np.int32).max,
                              dtype=jnp.int32)


# ---------------------------------------------------------------------------
# The blocked kernels' tile schedule.
#
# A blocked call (nq > 1 or nk > 1) walks a LIST of [block_q, block_k] score
# tiles, not the nq x nk square: the grid is (heads, steps), and two tables
# on the scalar-prefetch channel give each step its q block and k block, for
# the kernel and for every BlockSpec's index map. Under `causal` the list
# holds the tiles on and below the diagonal only, so a tile above it costs
# neither a grid step nor a fetch; under a sliding `window` (query i sees keys
# i - window < j <= i) it holds the band's tiles only, so neither does a tile
# whose keys all lie `window` or more behind every query of its q block. The
# forward (and the dq kernel of a
# two-kernel backward) lists them q-block-major (their accumulators belong
# to a q block), the backward k-block-major. A run of steps with the same
# major block is one accumulation: its first step zeroes the scratch, its
# last writes the output block.
# ---------------------------------------------------------------------------

def _tile_visible(iq, ik, block_q, block_k, window=None):
    """Some key of k block `ik` is at or before some query of q block `iq`,
    and under a `window` less than `window` behind one: the block's newest
    key against the q block's oldest query."""
    visible = ik * block_k <= iq * block_q + block_q - 1
    if window is None:
        return visible
    return visible & (iq * block_q - (ik * block_k + block_k - 1) < window)


@functools.lru_cache(maxsize=None)
def _tile_schedule(nq, nk, block_q, block_k, causal, k_major=False,
                   whole_square=False, window=None):
    """(q block, k block) of each grid step, two int32 arrays, built once a
    shape. `whole_square` lists the tiles above the diagonal (and behind the
    window) too (the dq kernel of a causal call with a per-q bias must still
    zero their `dbias` blocks; they run no body)."""
    steps = np.arange(nq * nk, dtype=np.int32)
    if k_major:
        ik, iq = np.divmod(steps, nq)
    else:
        iq, ik = np.divmod(steps, nk)
    if causal and not whole_square:
        visited = _tile_visible(iq, ik, block_q, block_k, window)
        iq, ik = iq[visited], ik[visited]
    return iq, ik


def _scores_visible(t, causal, window=None):
    """Scores a head of a [t, t] call must compute: the square, the causal
    half with its diagonal, or the band (a query's own key and the
    `window - 1` before it)."""
    if not causal:
        return t * t
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _record_tiles(kernel, nq, nk, block_q, block_k, causal, window=None):
    """Static numbers a head of the last blocked call of its kind traced
    (`call`: `full`, `causal` or `window`): the whole square, the steps that
    run a body, those that apply the mask (under `causal` every visited
    tile: a second body without it for the tiles wholly below the diagonal
    measured 0.0-0.4 ms a call and doubled what tracing a kernel costs,
    PERF.md section 6, PR 36), the scores the scheduled tiles compute and
    those of them the call needs."""
    from ...observability import get_registry
    visited = len(_tile_schedule(nq, nk, block_q, block_k, causal,
                                 window=window)[0])
    labels = dict(kernel=kernel, call=("window" if window is not None
                                       else "causal" if causal else "full"))
    reg = get_registry()
    reg.gauge("flash_attention/tiles_grid", **labels).set(nq * nk)
    reg.gauge("flash_attention/tiles_scheduled", **labels).set(visited)
    reg.gauge("flash_attention/tiles_masked", **labels).set(
        visited if causal else 0)
    reg.gauge("flash_attention/scores_scheduled", **labels).set(
        visited * block_q * block_k)
    reg.gauge("flash_attention/scores_visible", **labels).set(
        _scores_visible(nq * block_q, causal, window))


def _run_ends(major_ref, step, n_steps):
    """(first, last): whether `step` opens / closes its run of steps with
    the same major block."""
    cur = major_ref[step]
    first = jnp.logical_or(
        step == 0, major_ref[jnp.maximum(step - 1, 0)] != cur)
    last = jnp.logical_or(
        step == n_steps - 1,
        major_ref[jnp.minimum(step + 1, n_steps - 1)] != cur)
    return first, last


def _causal_mask(iq, ik, block_q, block_k, transposed=False, window=None):
    """[bq, bk] (or [bk, bq]) bool: query position >= key position, and
    under a `window` less than `window` past it."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_pos = iq * block_q + lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    k_pos = ik * block_k + lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _blocked_params(block_q, block_k, head_bytes=0):
    """Compiler parameters of a blocked kernel: a body holds a handful of
    float32 copies of its [block_q, block_k] tile (scores, probabilities
    and, backward, their two cotangents) beside the double-buffered
    operands, which at blocks of 1,024 is over Mosaic's default 16 MiB of a
    v5e's 128. `head_bytes` is what the kernel keeps for a whole head
    besides (the backward's dq: `_dq_head_bytes`)."""
    tile = block_q * block_k * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=min(100 * 2 ** 20,
                             16 * 2 ** 20 + 12 * tile + head_bytes))


def _tile_specs(kv, block_q, block_k, d, bias, dv=None):
    """BlockSpecs of a blocked call's operands by name. An index map takes
    (head, step, seed, q-block table, k-block table): `q` / `k` are
    [block, d] blocks of the step's q / k block (`k` of the query head's
    key/value head, `k_out` of the query head's own row), `o` / `v` /
    `v_out` the same blocks at the value head size `dv` (out and its
    cotangent, v, dv), `rows` a q block's row of statistics, `bias` the
    bias's block in the form it has."""
    dv = d if dv is None else dv

    def at(f):
        return lambda b, s, _, qi, ki: f(b, qi[s], ki[s])

    specs = {
        "q": pl.BlockSpec((1, block_q, d), at(lambda b, i, j: (b, i, 0))),
        "k": pl.BlockSpec((1, block_k, d), at(lambda b, i, j: (kv(b), j, 0))),
        "k_out": pl.BlockSpec((1, block_k, d),
                              at(lambda b, i, j: (b, j, 0))),
        "o": pl.BlockSpec((1, block_q, dv), at(lambda b, i, j: (b, i, 0))),
        "v": pl.BlockSpec((1, block_k, dv),
                          at(lambda b, i, j: (kv(b), j, 0))),
        "v_out": pl.BlockSpec((1, block_k, dv),
                              at(lambda b, i, j: (b, j, 0))),
        "rows": pl.BlockSpec((1, 1, 1, block_q),
                             at(lambda b, i, j: (b, i, 0, 0))),
        "tile": pl.BlockSpec((1, block_q, block_k),
                             at(lambda b, i, j: (b, i, j))),
        "col": pl.BlockSpec((1, 1, block_k), at(lambda b, i, j: (b, 0, j))),
    }
    if bias is not None:
        specs["bias"] = specs["tile" if bias.shape[1] != 1 else "col"]
    return specs


# The row statistics (lse, delta) travel compact, [BH, nq, 1, block_q]
# float32: a q block's statistics are one lane-major row of block_q
# numbers. On transposed scores (dk/dv) the row broadcasts down sublanes as
# it is; forward and dq turn it into / from a [block_q, 1] column once a
# step, a relayout of block_q numbers beside a tile of block_q x block_k.

def _rows(x, nq, block_q):
    """[BH, T] -> [BH, nq, 1, block_q]."""
    return x.reshape(x.shape[0], nq, 1, block_q)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, qi_ref, ki_ref, q_ref, k_ref, v_ref, bias_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, *, sm_scale, causal,
                block_q, block_k, nq, nk, n_steps, dropout_rate, window=None):
    b, step = pl.program_id(0), pl.program_id(1)
    iq, ik = qi_ref[step], ki_ref[step]
    first, last = _run_ends(qi_ref, step, n_steps)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        # native-dtype operands (bf16 under AMP → bf16 MXU inputs), f32 accum
        q = q_ref[0]                                          # [bq, D]
        k = k_ref[0]                                          # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [bq, bk]
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)           # [bq or 1, bk]
        if causal:
            s = jnp.where(
                _causal_mask(iq, ik, block_q, block_k, window=window), s,
                _NEG_INF)

        m_prev = m_ref[:, :1]                                 # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)            # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                        # [bq, 1]
        # denominator uses the *undropped* probabilities (dropout acts on
        # normalized attention probs; masking/scaling commutes with the
        # final division by l)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref, _block_index(b, iq, ik, nq, nk),
                              (block_q, block_k), dropout_rate)
            p_v = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
        else:
            p_v = p
        v_blk = v_ref[0]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p_v.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    _body()

    @pl.when(last)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l_safe)                  # [bq, 1]
        lse_ref[0, 0] = lse.reshape(1, block_q).astype(jnp.float32)


def _kv_row(kv_group: int):
    """Folded q row -> folded k/v row for the kernels' block index maps:
    with `kv_group` query heads to a key/value head (grouped-query
    attention) q row b*Hq + h reads k/v row b*Hkv + h // kv_group, which is
    (q row) // kv_group. One head each is the identity, so that the kernels
    of a model without grouped heads are built as before."""
    if kv_group == 1:
        return lambda b: b
    return lambda b: b // kv_group


def _flash_fwd_pallas(q, k, v, bias, sm_scale, causal, block_q, block_k,
                      interpret=False, dropout_rate=0.0, seed=None,
                      kv_group=1, window=None):
    """q: [BHq, T, D], k: [BHq / kv_group, T, D], v: [BHq / kv_group, T, Dv]
    (heads folded; the value head size Dv may differ from D); bias:
    [BHq, Tq_or_1, Tk] or None; `window` (with `causal`): a query sees its
    own key and the `window - 1` before it. Returns (out [BHq,T,Dv],
    lse [BHq,T])."""
    bh, t, d = q.shape
    dv = v.shape[2]
    block_q, block_k = min(block_q, t), min(block_k, t)
    nq, nk = t // block_q, t // block_k
    kv = _kv_row(kv_group)
    if nq == 1 and nk == 1 and kv_group == 1:
        per_q_bias = bias is not None and bias.shape[1] != 1
        group = _pick_group(
            bh, t, max(d, dv),
            _tt_bytes_per_head(1, per_q_bias, dropout_rate, t))
        if group > 1:
            return _flash_fwd_pallas_onepass(
                q, k, v, bias, sm_scale, causal, group, interpret=interpret,
                dropout_rate=dropout_rate, seed=seed, window=window)
    qi, ki = _tile_schedule(nq, nk, block_q, block_k, causal, window=window)
    n_steps = len(qi)
    _record_tiles("fwd", nq, nk, block_q, block_k, causal, window)

    specs = _tile_specs(kv, block_q, block_k, d, bias, dv)
    in_specs = [specs["q"], specs["k"], specs["v"]]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(specs["bias"])
        args.append(bias)

    body = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                             block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                             n_steps=n_steps, dropout_rate=dropout_rate,
                             window=window)
    if bias is not None:
        kernel = body
    else:
        def kernel(seed_ref, qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref,
                   lse_ref, acc, m, l):
            body(seed_ref, qi_ref, ki_ref, q_ref, k_ref, v_ref, None, o_ref,
                 lse_ref, acc, m, l)

    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)

    out, lse = kernel_call(
        "flash_fwd", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, n_steps),
            in_specs=in_specs,
            out_specs=[specs["o"], specs["rows"]],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, nq, 1, block_q), jnp.float32),
        ],
        compiler_params=_blocked_params(block_q, block_k),
        interpret=interpret,
    )(seed, jnp.asarray(qi), jnp.asarray(ki), *args)
    # the residual is lse compact, [BH, T]: a lane-replicated form would
    # cost 128x the memory between forward and backward
    return out, lse.reshape(bh, t)


# ---------------------------------------------------------------------------
# One-pass grouped kernels (T fits one block, i.e. nq == nk == 1).
#
# The general kernels pay a fixed per-grid-step cost (DMA setup, online-
# softmax stats corrections) on a grid of BH tiny steps — measured 14% MXU
# on BERT-base shapes (BH=768, T=512, D=64). When the whole sequence fits a
# single block the online softmax is unnecessary; these kernels batch G
# heads per grid step (BlockSpec (G, T, D) on the folded layout — leading-
# dim blocking, so no 64-wide minor slicing, unlike the rejected head-
# native path below) and compute plain softmax in one pass. Dropout masks
# are generated PER HEAD with the head's global index, so they are
# identical to the non-grouped kernels' masks (whose block index reduces to
# `b` when nq == nk == 1) — fwd and bwd may even pick different group sizes.
# ---------------------------------------------------------------------------

def _causal_mask_full(t, window=None):
    q_pos = lax.broadcasted_iota(jnp.int32, (t, t), 0)
    k_pos = lax.broadcasted_iota(jnp.int32, (t, t), 1)
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _group_keep_mask(seed_ref, g0, group, t, rate):
    """[G, T, T] keep mask; per-head streams keyed by global head index."""
    rows = []
    for i in range(group):
        rows.append(_keep_mask(seed_ref, g0 * group + i, (t, t), rate))
    return jnp.stack(rows)


def _fwd_kernel_onepass(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                        lse_ref, *, sm_scale, causal, dropout_rate, group,
                        window=None):
    g0 = pl.program_id(0)
    q, k, v = q_ref[...], k_ref[...], v_ref[...]          # [G, T, D]
    t = q.shape[1]
    s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[...].astype(jnp.float32)         # [G, Tq or 1, T]
    if causal:
        s = jnp.where(_causal_mask_full(t, window)[None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                # [G, T, 1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if dropout_rate > 0.0:
        keep = _group_keep_mask(seed_ref, g0, group, t, dropout_rate)
        p_v = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    else:
        p_v = p
    acc = lax.dot_general(p_v.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                          preferred_element_type=jnp.float32)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l_safe),
                                    lse_ref.shape).astype(jnp.float32)


def _flash_fwd_pallas_onepass(q, k, v, bias, sm_scale, causal, group,
                              interpret=False, dropout_rate=0.0, seed=None,
                              window=None):
    bh, t, d = q.shape
    dv = v.shape[2]
    grid = (bh // group,)
    in_specs = [pl.BlockSpec((group, t, d), lambda b, *_: (b, 0, 0))] * 2 + [
        pl.BlockSpec((group, t, dv), lambda b, *_: (b, 0, 0))]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((group, bias.shape[1], t),
                                     lambda b, *_: (b, 0, 0)))
        args.append(bias)

    body = functools.partial(_fwd_kernel_onepass, sm_scale=sm_scale,
                             causal=causal, dropout_rate=dropout_rate,
                             group=group, window=window)
    if bias is not None:
        kernel = body
    else:
        def kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
            body(seed_ref, q_ref, k_ref, v_ref, None, o_ref, lse_ref)

    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    out, lse = kernel_call(
        "flash_fwd_onepass", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((group, t, dv), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((group, t, _LANES), lambda b, *_: (b, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, t, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(seed, *args)
    return out, lse[:, :, 0]


def _bwd_kernel_onepass(seed_ref, q_ref, k_ref, v_ref, bias_ref, g_ref,
                        lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                        dbias_ref, dbias_col_ref, *, sm_scale, causal,
                        dropout_rate, group, window=None):
    g0 = pl.program_id(0)
    q, k, v, g = q_ref[...], k_ref[...], v_ref[...], g_ref[...]  # [G, T, D]
    t = q.shape[1]
    s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32) * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[...].astype(jnp.float32)
    if causal:
        s = jnp.where(_causal_mask_full(t, window)[None], s, _NEG_INF)
    lse = lse_ref[:, :, :1]                                # [G, T, 1]
    p = jnp.exp(s - lse)                                   # [G, T, T]
    if dropout_rate > 0.0:
        keep = _group_keep_mask(seed_ref, g0, group, t, dropout_rate)
        inv = 1.0 / (1.0 - dropout_rate)
        p_d = jnp.where(keep, p, 0.0) * inv
    else:
        p_d = p
    # dv = p_dropᵀ · dO  (contract over q)
    dv = lax.dot_general(p_d.astype(g.dtype), g, (((1,), (1,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    dp = lax.dot_general(g, v, (((2,), (2,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)  # [G, T, T]
    if dropout_rate > 0.0:
        dp = jnp.where(keep, dp * inv, 0.0)
    ds = p * (dp - delta_ref[:, :, :1])                    # [G, T, T]
    ds_c = ds.astype(q.dtype)
    dk = lax.dot_general(ds_c, q, (((1,), (1,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    dq = lax.dot_general(ds_c, k, (((2,), (1,)), ((0,), (0,))),
                         preferred_element_type=jnp.float32)
    dq_ref[...] = (dq * sm_scale).astype(dq_ref.dtype)
    dk_ref[...] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    if dbias_ref is not None:
        dbias_ref[...] = ds.astype(dbias_ref.dtype)
    if dbias_col_ref is not None:
        dbias_col_ref[...] = jnp.sum(ds, axis=1, keepdims=True).astype(
            dbias_col_ref.dtype)


def _bwd_host_prep(q, g, lse, out):
    """Shared residual preprocessing for both backward wrappers.

    delta = Σ_d dO·out; lse/delta are lane-replicated for the kernels. The
    optimization_barrier ties the lse broadcast to g: without the data
    dependency XLA's scheduler hoists every layer's 128-lane-replicated
    broadcast to the start of the backward and keeps them all live
    (~190 MB × layers); a `+ 0*g[0]` tie would instead propagate a single
    inf/NaN to every row."""
    bh, t, _ = q.shape
    gf = g.astype(q.dtype)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse, _ = lax.optimization_barrier((lse, gf))
    lse_r = jnp.broadcast_to(lse[:, :, None], (bh, t, _LANES))
    delta_r = jnp.broadcast_to(delta[:, :, None], (bh, t, _LANES))
    return gf, lse_r, delta_r


def _flash_bwd_pallas_onepass(q, k, v, bias, g, lse, out, sm_scale, causal,
                              group, dropout_rate=0.0, seed=None,
                              interpret=False, window=None):
    bh, t, d = q.shape
    dv = v.shape[2]
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    gf, lse_r, delta_r = _bwd_host_prep(q, g, lse, out)

    has_bias = bias is not None
    per_q_bias = has_bias and bias.shape[1] != 1
    col_bias = has_bias and not per_q_bias

    qk_spec = pl.BlockSpec((group, t, d), lambda b, *_: (b, 0, 0))
    v_spec = pl.BlockSpec((group, t, dv), lambda b, *_: (b, 0, 0))
    in_specs = [qk_spec, qk_spec, v_spec]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((group, bias.shape[1], t),
                                     lambda b, *_: (b, 0, 0)))
        args.append(bias)
    in_specs += [
        pl.BlockSpec((group, t, dv), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec((group, t, _LANES), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec((group, t, _LANES), lambda b, *_: (b, 0, 0)),
    ]
    args += [gf, lse_r, delta_r]

    out_specs = [qk_spec, qk_spec, v_spec]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
    if per_q_bias:
        out_specs.append(pl.BlockSpec((group, t, t), lambda b, *_: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, t, t), jnp.float32))
    if col_bias:
        out_specs.append(pl.BlockSpec((group, 1, t), lambda b, *_: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, t), jnp.float32))

    body = functools.partial(_bwd_kernel_onepass, sm_scale=sm_scale,
                             causal=causal, dropout_rate=dropout_rate,
                             group=group, window=window)

    def kernel(seed_ref, *refs):
        n_in = 6 + (1 if has_bias else 0)
        ins, outs = refs[:n_in], refs[n_in:]
        if has_bias:
            q_r, k_r, v_r, b_r, g_r, l_r, d_r = ins
        else:
            (q_r, k_r, v_r, g_r, l_r, d_r), b_r = ins, None
        dq_r, dk_r, dv_r = outs[:3]
        db_r = outs[3] if per_q_bias else None
        dbc_r = outs[3] if col_bias else None
        body(seed_ref, q_r, k_r, v_r, b_r, g_r, l_r, d_r,
             dq_r, dk_r, dv_r, db_r, dbc_r)

    res = kernel_call(
        "flash_bwd_onepass", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh // group,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(seed, *args)
    dq, dk, dv = res[:3]
    dbias = res[3] if has_bias else None
    return dq, dk, dv, dbias


def _tt_bytes_per_head(base, per_q_bias, dropout_rate, t):
    """Bytes of concurrently-live [T, T]-sized per-head buffers: `base` f32
    intermediates (1 fwd: s/p; 3 bwd: p, dp, ds), the per-q bias input and
    (bwd) dbias output, and the 1-byte dropout keep mask."""
    n_f32 = base + (2 if per_q_bias and base > 1 else 1 if per_q_bias else 0)
    mask = t * t if dropout_rate > 0.0 else 0
    return n_f32 * t * t * 4 + mask


def _pick_group(bh, t, d, tt_bytes, budget=10 * 2 ** 20):
    """Heads per grid step for the one-pass kernels. `tt_bytes` is the
    per-head [T, T]-buffer footprint (see _tt_bytes_per_head); exceeding
    the budget falls back to the general blocked kernels, which is always
    correct."""
    for g in (8, 4, 2):
        need = g * (tt_bytes + 6 * t * d * 4 + 2 * t * _LANES * 4)
        if bh % g == 0 and need <= budget:
            return g
    return 1


# ---------------------------------------------------------------------------
# Pallas backward kernels (flash recompute from saved lse)
#
#   delta = Σ_d dO·out                              (precomputed, [BH,T])
#   p  = exp(s − lse)                               (recomputed per block)
#   dv = p_dropᵀ·dO          dp = dO·vᵀ (drop-scaled)
#   ds = p·(dp − delta)      dk = dsᵀ·q·scale       dq = Σ_j ds·k·scale
#
# Five products a tile, and `_bwd_dkv_kernel` makes all five where the
# head's dq fits VMEM beside it (`_dq_in_dkv`): a k block's run adds to dk
# and dv, every tile adds to its q block's rows of a [T, D] float32 dq that
# lives from the head's first step to its last. The k blocks come in
# ascending order, so a q block's sum runs in the order `_bwd_dq_kernel`
# sums it. That kernel, which makes s and dp a second time, runs only
# beside a dk/dv kernel that leaves dq out.
# ---------------------------------------------------------------------------

# VMEM the dk/dv kernel may spend on a whole head's dq: the float32
# accumulator and the two buffers of its output block. 12.6 MB at T 8,192
# with heads of 192 in bf16, 8.4 at 128; T 32,768 at 128 no longer fits.
_DQ_HEAD_BUDGET = 24 * 2 ** 20


def _dq_head_bytes(t, d, dtype):
    return t * d * (4 + 2 * jnp.dtype(dtype).itemsize)


def _dq_in_dkv(t, d, dtype, per_q_bias):
    """Whether a blocked backward is the one kernel. A per-q bias keeps the
    dq kernel: its gradient is the [T, T] `ds` itself, written tile by tile
    q-block-major, zeroed above the diagonal."""
    return not per_q_bias and _dq_head_bytes(t, d, dtype) <= _DQ_HEAD_BUDGET


def _bwd_dq_kernel(seed_ref, qi_ref, ki_ref, q_ref, k_ref, v_ref, bias_ref,
                   g_ref, lse_ref, delta_ref, dq_ref, dbias_ref, dq_acc, *,
                   sm_scale, causal, block_q, block_k, nq, nk, n_steps,
                   dropout_rate, window=None):
    b, step = pl.program_id(0), pl.program_id(1)
    iq, ik = qi_ref[step], ki_ref[step]
    first, last = _run_ends(qi_ref, step, n_steps)

    @pl.when(first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        q = q_ref[0]                                          # [bq, D]
        k = k_ref[0]                                          # [bk, D]
        v = v_ref[0]                                          # [bk, D]
        g = g_ref[0]                                          # [bq, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [bq, bk]
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            s = jnp.where(
                _causal_mask(iq, ik, block_q, block_k, window=window), s,
                _NEG_INF)
        lse = lse_ref[0, 0].reshape(block_q, 1)               # [bq, 1]
        p = jnp.exp(s - lse)                                  # [bq, bk]
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref, _block_index(b, iq, ik, nq, nk),
                              (block_q, block_k), dropout_rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        delta = delta_ref[0, 0].reshape(block_q, 1)           # [bq, 1]
        ds = p * (dp - delta)                                 # [bq, bk] f32
        if dbias_ref is not None:
            dbias_ref[0] = ds.astype(dbias_ref.dtype)
        ds_c = ds.astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds_c, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    if causal and dbias_ref is not None:
        # the whole square is scheduled: a tile above the diagonal runs no
        # body but still zeroes its block of the per-q bias gradient
        visible = _tile_visible(iq, ik, block_q, block_k, window)

        @pl.when(visible)
        def _():
            _body()

        @pl.when(jnp.logical_not(visible))
        def _():
            dbias_ref[0] = jnp.zeros_like(dbias_ref[0])
    else:
        _body()

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, qi_ref, ki_ref, q_ref, k_ref, v_ref, bias_ref,
                    g_ref, lse_ref, delta_ref, dk_ref, dv_ref, dbias_col_ref,
                    dq_ref, dk_acc, dv_acc, db_acc, dq_acc, *, sm_scale,
                    causal, block_q, block_k, nq, nk, n_steps, dropout_rate,
                    per_q_bias, window=None):
    """dk and dv of one k block, on TRANSPOSED scores: sᵀ = k·qᵀ is
    [bk, bq], so the q block's lse and delta are [1, bq] rows that broadcast
    down sublanes as they come, and dv += pᵀ·g, dk += dsᵀ·q contract the
    tile's lane axis like any product (on [bq, bk] scores both would
    contract the row axis, a transposition of the tile each). With `dq_ref`
    (a head's whole [T, D] block) the tile's dsᵀ also gives dq: the one
    product that contracts the row axis, added to the q block's rows of
    `dq_acc`."""
    # the steps are k-block-major: a run is one k block's q blocks
    b, step = pl.program_id(0), pl.program_id(1)
    iq, ik = qi_ref[step], ki_ref[step]
    first, last = _run_ends(ki_ref, step, n_steps)

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if db_acc is not None:
            db_acc[...] = jnp.zeros_like(db_acc)

    if dq_acc is not None:
        @pl.when(step == 0)
        def _init_head():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        q = q_ref[0]                                          # [bq, D]
        k = k_ref[0]                                          # [bk, D]
        v = v_ref[0]                                          # [bk, D]
        g = g_ref[0]                                          # [bq, D]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [bk, bq]
        if bias_ref is not None:
            bias = bias_ref[0].astype(jnp.float32)            # [bq or 1, bk]
            st = st + (bias.T if per_q_bias
                       else bias.reshape(block_k, 1))
        if causal:
            st = jnp.where(
                _causal_mask(iq, ik, block_q, block_k, transposed=True,
                             window=window), st,
                _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0])                      # [bk, bq]
        dpt = jax.lax.dot_general(
            v, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, bq]
        if dropout_rate > 0.0:
            # the forward's [bq, bk] draw for this (b, iq, ik), transposed
            keep = _keep_mask(seed_ref, _block_index(b, iq, ik, nq, nk),
                              (block_q, block_k), dropout_rate,
                              transposed=True)
            inv = 1.0 / (1.0 - dropout_rate)
            pt_v = jnp.where(keep, pt * inv, 0.0)
            dpt = jnp.where(keep, dpt * inv, 0.0)
        else:
            pt_v = pt
        dv_acc[...] += jax.lax.dot_general(
            pt_v.astype(g.dtype), g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, D]
        dst = pt * (dpt - delta_ref[0, 0])                    # [bk, bq] f32
        dst_c = dst.astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            dst_c, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [bk, D]
        if db_acc is not None:
            db_acc[...] += jnp.sum(dst, axis=1, keepdims=True)  # [bk, 1]
        if dq_acc is not None:
            rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
            dq_acc[rows, :] += jax.lax.dot_general(
                dst_c, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [bq, D]

    _body()

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        if dbias_col_ref is not None:
            dbias_col_ref[0] = db_acc[...].reshape(1, block_k).astype(
                dbias_col_ref.dtype)

    if dq_acc is not None:
        @pl.when(step == n_steps - 1)
        def _finalize_head():
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, bias, g, lse, out, sm_scale, causal,
                      block_q, block_k, dropout_rate=0.0, seed=None,
                      interpret=False, kv_group=1, window=None):
    """Returns (dq, dk, dv, dbias). dbias is [BH,Tq,Tk] f32 for a per-q bias,
    [BH,1,Tk] f32 for a broadcast (mask-like) bias, or None. With
    `kv_group` query heads to a key/value head, k and v are
    [BH / kv_group, T, D] and dk, dv come back per QUERY head ([BH, T, D]):
    the caller sums them over the group. v, g and out may have a head size
    Dv of their own ([.., T, Dv]); dv then has it too."""
    bh, t, d = q.shape
    dv_ = v.shape[2]
    block_q, block_k = min(block_q, t), min(block_k, t)
    nq, nk = t // block_q, t // block_k
    kv = _kv_row(kv_group)
    if nq == 1 and nk == 1 and kv_group == 1:
        per_q_bias = bias is not None and bias.shape[1] != 1
        group = _pick_group(
            bh, t, max(d, dv_),
            _tt_bytes_per_head(3, per_q_bias, dropout_rate, t))
        if group > 1:
            return _flash_bwd_pallas_onepass(
                q, k, v, bias, g, lse, out, sm_scale, causal, group,
                dropout_rate=dropout_rate, seed=seed, interpret=interpret,
                window=window)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)

    has_bias = bias is not None
    per_q_bias = has_bias and bias.shape[1] != 1
    col_bias = has_bias and not per_q_bias

    gf = g.astype(q.dtype)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    stats = [_rows(lse, nq, block_q), _rows(delta, nq, block_q)]
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, nq=nq, nk=nk, dropout_rate=dropout_rate,
                  window=window)

    specs = _tile_specs(kv, block_q, block_k, d, bias, dv_)
    in_specs = [specs["q"], specs["k"], specs["v"]]
    args = [q, k, v]
    if has_bias:
        in_specs.append(specs["bias"])
        args.append(bias)
    in_specs += [specs["o"], specs["rows"], specs["rows"]]   # g, lse, delta
    args += [gf, *stats]
    n_in = len(args)

    def split_inputs(refs):
        """(q, k, v, bias or None, g, lse, delta), the rest."""
        ins = list(refs[:n_in])
        if not has_bias:
            ins.insert(3, None)
        return ins, refs[n_in:]

    one_kernel = _dq_in_dkv(t, d, q.dtype, per_q_bias)
    dq = dbias = None
    if not one_kernel:
        # ---- dq kernel: q-block-major steps --------------------------------
        qi, ki = _tile_schedule(nq, nk, block_q, block_k, causal,
                                whole_square=per_q_bias, window=window)
        _record_tiles("dq", nq, nk, block_q, block_k, causal, window)
        out_specs = [specs["q"]]
        out_shape = [jax.ShapeDtypeStruct((bh, t, d), q.dtype)]
        if per_q_bias:
            out_specs.append(specs["tile"])
            out_shape.append(jax.ShapeDtypeStruct((bh, t, t), jnp.float32))

        body = functools.partial(_bwd_dq_kernel, n_steps=len(qi), **static)

        def dq_kernel(seed_ref, qi_ref, ki_ref, *refs):
            ins, outs = split_inputs(refs)
            if per_q_bias:
                dq_r, db_r, acc = outs
            else:
                (dq_r, acc), db_r = outs, None
            body(seed_ref, qi_ref, ki_ref, *ins, dq_r, db_r, acc)

        dq_out = kernel_call(
            "flash_bwd_dq", dq_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(bh, len(qi)),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            ),
            out_shape=out_shape,
            compiler_params=_blocked_params(block_q, block_k),
            interpret=interpret,
        )(seed, jnp.asarray(qi), jnp.asarray(ki), *args)
        dq = dq_out[0]
        if per_q_bias:
            dbias = dq_out[1]

    # ---- dk/dv (and dq) kernel: k-block-major steps, transposed scores -----
    qi, ki = _tile_schedule(nq, nk, block_q, block_k, causal, k_major=True,
                            window=window)
    _record_tiles("bwd" if one_kernel else "dkv", nq, nk, block_q, block_k,
                  causal, window)
    out_specs2 = [specs["k_out"], specs["v_out"]]
    out_shape2 = [
        jax.ShapeDtypeStruct((bh, t, d), k.dtype),
        jax.ShapeDtypeStruct((bh, t, dv_), v.dtype),
    ]
    scratch2 = [pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, dv_), jnp.float32)]
    if col_bias:
        out_specs2.append(specs["col"])
        out_shape2.append(jax.ShapeDtypeStruct((bh, 1, t), jnp.float32))
        scratch2.append(pltpu.VMEM((block_k, 1), jnp.float32))
    if one_kernel:
        # the head's whole dq: a block that changes with the head alone
        out_specs2.append(pl.BlockSpec((1, t, d), lambda b, *_: (b, 0, 0)))
        out_shape2.append(jax.ShapeDtypeStruct((bh, t, d), q.dtype))
        scratch2.append(pltpu.VMEM((t, d), jnp.float32))

    body2 = functools.partial(_bwd_dkv_kernel, n_steps=len(qi),
                              per_q_bias=per_q_bias, **static)

    def dkv_kernel(seed_ref, qi_ref, ki_ref, *refs):
        ins, rest = split_inputs(refs)
        outs, accs = (list(rest[:len(out_specs2)]),
                      list(rest[len(out_specs2):]))
        # (dk, dv, dbias column, dq) and their accumulators, an absent
        # pair None
        for slot, present in ((2, col_bias), (3, one_kernel)):
            if not present:
                outs.insert(slot, None)
                accs.insert(slot, None)
        body2(seed_ref, qi_ref, ki_ref, *ins, *outs, *accs)

    dkv_out = kernel_call(
        "flash_bwd" if one_kernel else "flash_bwd_dkv", dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, len(qi)),
            in_specs=in_specs,
            out_specs=out_specs2,
            scratch_shapes=scratch2,
        ),
        out_shape=out_shape2,
        compiler_params=_blocked_params(
            block_q, block_k,
            _dq_head_bytes(t, d, q.dtype) if one_kernel else 0),
        interpret=interpret,
    )(seed, jnp.asarray(qi), jnp.asarray(ki), *args)
    dk, dv = dkv_out[:2]
    if col_bias:
        dbias = dkv_out[2]
    if one_kernel:
        dq = dkv_out[-1]
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# Blockwise JAX path (CPU tests / fallback) — same math, two passes
# ---------------------------------------------------------------------------

def _bias_block(bias, j0, bk):
    if bias is None:
        return 0.0
    return lax.dynamic_slice_in_dim(bias, j0, bk, axis=-1).astype(jnp.float32)


def _scores(q, k_blk, bias, j0, causal, sm_scale, bk, window=None):
    # q: [BH, Tq, D], k_blk: [BH, bk, D] → s: [BH, Tq, bk]
    # native-dtype operands (bf16 under AMP), f32 accumulation
    s = jnp.einsum("bqd,bkd->bqk", q, k_blk,
                   preferred_element_type=jnp.float32) * sm_scale
    s = s + _bias_block(bias, j0, bk)
    if causal:
        tq = q.shape[1]
        q_pos = lax.broadcasted_iota(jnp.int32, (tq, bk), 0)
        k_pos = j0 + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
        visible = q_pos >= k_pos
        if window is not None:
            visible = visible & (q_pos - k_pos < window)
        s = jnp.where(visible, s, _NEG_INF)
    return s


def _flash_fwd_jax(q, k, v, bias, sm_scale, causal, block_k,
                   dropout_rate=0.0, dropout_key=None, window=None):
    """Two-pass online softmax: pass 1 → (m, lse); pass 2 → output.
    Handles attention-prob dropout (regenerated per block from a folded key,
    so the backward recompute sees identical masks)."""
    bh, t, d = q.shape
    nk = t // block_k

    def pass1(carry, j):
        m, l = carry
        s = _scores(q, lax.dynamic_slice_in_dim(k, j * block_k, block_k, 1),
                    bias, j * block_k, causal, sm_scale, block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new), -1, keepdims=True)
        return (m_new, l), None

    m0 = jnp.full((bh, t, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, t, 1), jnp.float32)
    (m, l), _ = lax.scan(pass1, (m0, l0), jnp.arange(nk))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    lse = (m + jnp.log(l_safe))[..., 0]

    def pass2(acc, j):
        s = _scores(q, lax.dynamic_slice_in_dim(k, j * block_k, block_k, 1),
                    bias, j * block_k, causal, sm_scale, block_k, window)
        p = jnp.exp(s - lse[..., None])
        p = _apply_dropout(p, dropout_rate, dropout_key, j)
        v_blk = lax.dynamic_slice_in_dim(v, j * block_k, block_k, 1)
        acc = acc + jnp.einsum("bqk,bkd->bqd", p.astype(v_blk.dtype), v_blk,
                               preferred_element_type=jnp.float32)
        return acc, None

    out, _ = lax.scan(pass2, jnp.zeros((bh, t, v.shape[2]), jnp.float32),
                      jnp.arange(nk))
    return out.astype(q.dtype), lse


def _apply_dropout(p, rate, key, block_idx):
    if rate == 0.0 or key is None:
        return p
    keep = jax.random.bernoulli(jax.random.fold_in(key, block_idx),
                                1.0 - rate, p.shape)
    return jnp.where(keep, p / (1.0 - rate), 0.0)


def _flash_bwd_jax(res, g, *, sm_scale, causal, block_k,
                   dropout_rate, has_bias, window=None):
    """Flash backward: scan KV blocks, recompute p from (q,k,lse); per block
    dv_j = pᵀ·dO, ds = p∘(dO·vᵀ − D), dk_j = dsᵀ·q, dq += ds·k."""
    q, k, v, bias, dropout_key, out, lse = res
    bh, t, d = q.shape
    nk = t // block_k
    cdt = q.dtype  # MXU operand dtype (bf16 under AMP); accumulations f32
    gc = g.astype(cdt)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                        # [BH,T,1]

    def step(dq, j):
        j0 = j * block_k
        k_blk = lax.dynamic_slice_in_dim(k, j0, block_k, 1)
        v_blk = lax.dynamic_slice_in_dim(v, j0, block_k, 1)
        s = _scores(q, k_blk, bias, j0, causal, sm_scale, block_k, window)
        p = jnp.exp(s - lse[..., None])                            # [BH,T,bk]
        p_d = _apply_dropout(p, dropout_rate, dropout_key, j)
        dv_j = jnp.einsum("bqk,bqd->bkd", p_d.astype(cdt), gc,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqd,bkd->bqk", gc, v_blk.astype(cdt),
                        preferred_element_type=jnp.float32)
        if dropout_rate > 0.0 and dropout_key is not None:
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_key, j), 1.0 - dropout_rate, p.shape)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta)                                      # [BH,T,bk]
        dk_j = jnp.einsum("bqk,bqd->bkd", ds.astype(cdt), q.astype(cdt),
                          preferred_element_type=jnp.float32) * sm_scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds.astype(cdt), k_blk.astype(cdt),
                             preferred_element_type=jnp.float32) * sm_scale
        dbias_j = ds if has_bias else None
        return dq, (dk_j, dv_j, dbias_j)

    dq0 = jnp.zeros((bh, t, d), jnp.float32)
    dq, (dk_blocks, dv_blocks, dbias_blocks) = lax.scan(step, dq0, jnp.arange(nk))
    # [nk, BH, bk, d] → [BH, T, d]
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(bh, t, d)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(bh, t, v.shape[2])
    dbias = None
    if has_bias:
        # [nk, BH, Tq, bk] → [BH, Tq, nk, bk] → [BH, Tq, Tk]: the scanned
        # block axis must precede the within-block key axis before reshape
        dbias = jnp.moveaxis(dbias_blocks, 0, 2).reshape(bh, t, t)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dbias


# ---------------------------------------------------------------------------
# Packed-layout [B, T, H] public entry.
#
# A head-native Pallas path was measured and rejected: Mosaic requires
# 128-divisible (or full) minor block dims, so a per-head 64-wide column
# cannot be a block; head-batched tiles with in-kernel 64-lane slicing ran
# 3× slower than the folded kernels (VPU relayouts), and batched dots with
# batch dims in the middle don't lower at all ("batch dims pos must be 0").
# The packed API therefore adapts to the folded layout — XLA inserts the
# head-split transposes (~5% of a BERT-base step), which is the measured
# optimum on v5e for d=64 heads.
# ---------------------------------------------------------------------------

def _pack_to_folded(x, nh):
    b_, t, hdim = x.shape
    d = hdim // nh
    return x.reshape(b_, t, nh, d).transpose(0, 2, 1, 3).reshape(b_ * nh, t, d)


def _folded_to_pack(x, b_):
    bh, t, d = x.shape
    nh = bh // b_
    return x.reshape(b_, nh, t, d).transpose(0, 2, 1, 3).reshape(b_, t, nh * d)


def flash_attention_packed(q, k, v, num_heads: int, bias=None,
                           causal: bool = False,
                           sm_scale: Optional[float] = None,
                           dropout_rate: float = 0.0, dropout_key=None,
                           num_kv_heads: Optional[int] = None,
                           window: Optional[int] = None):
    """Memory-efficient attention on packed tensors: q [B, T, nh·d], k
    [B, T, nkv·d], v [B, T, nkv·dv].

    Two head sizes: `d` of q and k (the width the scores contract over,
    q's last dimension over `num_heads`) and `dv` of v and of the result
    (v's last dimension over the key/value heads); they are the same in
    most models and differ under latent attention (192 and 128).
    Adapts to the folded [B·nh, T, d] kernel layout; XLA inserts the
    head-split transposes (see the layout note above — measured optimum for
    d=64 heads on v5e). bias (optional) is the additive [B, 1, T] mask.
    `num_kv_heads` (a divisor of `num_heads`; default: the same) is the head
    count of k and v: query head h reads key/value head h // (nh / nkv).
    `sm_scale` defaults to d^-1/2, the q/k head size's. `window` (with
    `causal`): a sliding window, query i sees keys i - window < j <= i, its
    own among them; the kernels visit the band's tiles only. Returns
    [B, T, nh·dv]."""
    b_, t, hdim = q.shape
    num_kv_heads = num_heads if num_kv_heads is None else num_kv_heads
    if hdim % num_heads:
        raise ValueError(f"hidden {hdim} not divisible by heads {num_heads}")
    d = hdim // num_heads
    if (num_heads % num_kv_heads or k.shape[2] != num_kv_heads * d
            or v.shape[2] % num_kv_heads):
        raise ValueError(
            f"flash_attention: {num_kv_heads} key/value heads must divide "
            f"{num_heads} query heads, k be [B, T, {num_kv_heads * d}] (q's "
            f"head size {d}) and v [B, T, {num_kv_heads} x its own head "
            f"size], got k {k.shape}, v {v.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(
            f"flash_attention: dropout_rate must be in [0, 1), got "
            f"{dropout_rate}")
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError(
            "flash_attention: dropout_rate > 0 requires a dropout_key; "
            "pass one or set dropout_rate=0 for inference")
    if bias is not None:
        if bias.ndim != 3 or bias.shape[1] != 1:
            raise ValueError(
                f"packed flash_attention bias must be [B, 1, T], got "
                f"{bias.shape}")
        bias = jnp.broadcast_to(bias[:, None], (b_, num_heads, 1, t)).reshape(
            b_ * num_heads, 1, t)
    if dropout_rate == 0.0:
        dropout_key = None
    qf = _pack_to_folded(q, num_heads)
    kf, vf = (_pack_to_folded(x, num_kv_heads) for x in (k, v))
    out = _flash_core(qf, kf, vf, bias, dropout_key, float(sm_scale),
                      bool(causal), float(dropout_rate),
                      _checked_window(window, causal, t))
    return _folded_to_pack(out, b_)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def _checked_window(window, causal: bool, t: int) -> Optional[int]:
    """A caller's `window` as `_flash_core` takes it: None where there is no
    window or it covers the sequence (the call is then the causal one, to
    the bit), else the window checked."""
    if window is None:
        return None
    if isinstance(window, bool) or int(window) != window or window < 1:
        raise ValueError(f"flash_attention: window must be a whole number "
                         f"of keys >= 1 (a query's own among them), got "
                         f"{window!r}")
    if not causal:
        raise ValueError(
            "flash_attention: a window looks back from the query (keys "
            "i - window < j <= i) and needs causal=True; a window around "
            "the query is not built")
    return None if window >= t else int(window)


def _pick_blocks(t: int):
    bq = next((b for b in (DEFAULT_BLOCK_Q, 256, 128, 64, 32, 16, 8)
               if t % b == 0), None)
    return bq, bq


# Blocks of a call under a sliding window narrower than the dense default:
# what a v5e measured at T 8,192, window 512, heads of 128 (PERF.md section
# 6, PR 41).
_WINDOW_BLOCK = 512


def _pick_dense_blocks(t: int, window: Optional[int] = None):
    """Blocks of the dense kernels. From four blocks of 1,024 a side they are
    1,024 square: a step's costs that do not grow with its tile (the forward's
    row maxima and sums, a lane reduction of [block_q, 128] whatever block_k
    is, and the rescaling of its accumulators) are then paid a quarter as
    often, which on a v5e at T 8,192 takes the forward from 21.6 to 11.8 ms
    a call at head size 64 and from 21.3 to 11.1 at 128, and dq and dk/dv
    down by a tenth; at T 4,096, 3.7 to 2.4 (PERF.md section 6, PR 36).
    Larger or oblong blocks measured no better. Below that the blocks are
    `_pick_blocks`'s, as the block-sparse kernels' always are. Under a
    `window` narrower than those blocks a tile of 1,024 is three quarters
    masked: the blocks are `_WINDOW_BLOCK` then."""
    if t % 1024 == 0 and t >= 4 * 1024:
        if window is not None and window < 1024:
            return _WINDOW_BLOCK, _WINDOW_BLOCK
        return 1024, 1024
    return _pick_blocks(t)


def _pallas_ok(t: int, d: int, dv: Optional[int] = None) -> bool:
    """Static dispatch decision — must be identical in fwd and bwd so the
    in-kernel dropout masks regenerate consistently. `d` is the head size
    of q and k, `dv` that of v and out (default: the same)."""
    bq, _ = _pick_blocks(t)
    return ((_on_tpu() or FORCE_PALLAS_INTERPRET)
            and bq is not None and bq >= 64 and d % 64 == 0
            and (d if dv is None else dv) % 64 == 0)


def _interpret_arg(dropout_rate: float):
    if not FORCE_PALLAS_INTERPRET or _on_tpu():
        return False
    # dropout kernels call pltpu.prng_*, which only the TPU-semantics
    # interpreter accepts (it returns zero bits — numerics are TPU-only)
    return pltpu.InterpretParams() if dropout_rate > 0.0 else True


def _flash_bwd_block_dispatch(q, k, v, g, lse, out, sm_scale, causal):
    """Block-level backward for the RING path (parallel/ring_attention.py):
    given one resident K/V block and the GLOBAL lse/out/delta residuals,
    return (dq, dk, dv) for that block via the Pallas backward kernel
    (jax fallback off-TPU). No bias/dropout on the ring path."""
    t, d = q.shape[1], q.shape[2]
    bq, bk = _pick_dense_blocks(t)
    if _pallas_ok(t, d, v.shape[2]):
        dq, dk, dv, _ = _flash_bwd_pallas(
            q, k, v, None, g, lse, out, sm_scale, causal, bq, bk,
            interpret=_interpret_arg(0.0))
        return dq, dk, dv
    dq, dk, dv, _ = _flash_bwd_jax(
        (q, k, v, None, None, out, lse), g, sm_scale=sm_scale,
        causal=causal, block_k=bk or t, dropout_rate=0.0, has_bias=False)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(q, k, v, bias, dropout_key, sm_scale, causal, dropout_rate,
                window=None):
    out, _ = _flash_fwd_dispatch(q, k, v, bias, dropout_key, sm_scale,
                                 causal, dropout_rate, window)
    return out


def _kv_group(q, k) -> int:
    """Query heads to a key/value head, from the folded shapes."""
    return q.shape[0] // k.shape[0]


def _repeat_kv(x, group: int):
    """[BHkv, T, D] -> [BHq, T, D]: each k/v head once per query head of its
    group (the blockwise-JAX path; the kernels index instead)."""
    return x if group == 1 else jnp.repeat(x, group, axis=0)


def _sum_kv_group(dx, group: int, dtype):
    """Per-query-head dk or dv [BHq, T, D] -> [BHkv, T, D], summed in
    float32 over the heads that read the same key/value head."""
    if group == 1:
        return dx
    bh, t, d = dx.shape
    return jnp.sum(dx.astype(jnp.float32).reshape(bh // group, group, t, d),
                   axis=1).astype(dtype)


def _flash_fwd_dispatch(q, k, v, bias, dropout_key, sm_scale, causal,
                        dropout_rate, window=None):
    t, d = q.shape[1], q.shape[2]
    bq, bk = _pick_dense_blocks(t, window)
    group = _kv_group(q, k)
    if _pallas_ok(t, d, v.shape[2]):
        seed = (_seed_from_key(dropout_key) if dropout_rate > 0.0 else None)
        return _flash_fwd_pallas(q, k, v, bias, sm_scale, causal, bq, bk,
                                 dropout_rate=dropout_rate, seed=seed,
                                 interpret=_interpret_arg(dropout_rate),
                                 kv_group=group, window=window)
    if bq is None:
        raise ValueError(f"flash_attention: seq len {t} has no power-of-two "
                         f"block divisor ≥8; pad the sequence")
    key = dropout_key if dropout_rate > 0.0 else None
    return _flash_fwd_jax(q, _repeat_kv(k, group), _repeat_kv(v, group), bias,
                          sm_scale, causal, bk, dropout_rate, key,
                          window=window)


def _flash_core_fwd(q, k, v, bias, dropout_key, sm_scale, causal, dropout_rate,
                    window=None):
    out, lse = _flash_fwd_dispatch(q, k, v, bias, dropout_key, sm_scale,
                                   causal, dropout_rate, window)
    out, lse = _kept(out, KEPT[0]), _kept(lse, KEPT[1])
    key = dropout_key if dropout_rate > 0.0 else None
    return out, (q, k, v, bias, key, out, lse)


def _flash_core_bwd(sm_scale, causal, dropout_rate, window, res, g):
    q, k, v, bias, key, out, lse = res
    t, d = q.shape[1], q.shape[2]
    bq, bk = _pick_dense_blocks(t, window)
    has_bias = bias is not None
    group = _kv_group(q, k)
    if _pallas_ok(t, d, v.shape[2]):
        seed = (_seed_from_key(key) if dropout_rate > 0.0 else None)
        dq, dk, dv, dbias = _flash_bwd_pallas(
            q, k, v, bias, g, lse, out, sm_scale, causal, bq, bk,
            dropout_rate=dropout_rate, seed=seed,
            interpret=_interpret_arg(dropout_rate), kv_group=group,
            window=window)
    else:
        res = (q, _repeat_kv(k, group), _repeat_kv(v, group)) + res[3:]
        dq, dk, dv, dbias = _flash_bwd_jax(
            res, g, sm_scale=sm_scale, causal=causal, block_k=bk,
            dropout_rate=dropout_rate, has_bias=has_bias,
            window=window)
    dk = _sum_kv_group(dk, group, k.dtype)
    dv = _sum_kv_group(dv, group, v.dtype)
    if has_bias:
        # reduce over broadcast dims back to the bias shape (the pallas
        # col-sum path has already reduced the q axis)
        for ax in range(dbias.ndim):
            if bias.shape[ax] == 1 and dbias.shape[ax] != 1:
                dbias = jnp.sum(dbias, axis=ax, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    dkey = (None if key is None
            else np.zeros(np.shape(key), jax.dtypes.float0))
    return dq, dk, dv, dbias, dkey


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# Block-sparse packed-segment attention.
#
# Bucketed-length batches (reader.pack_by_tokens) carried a dense additive
# [B, 1, Tq, Tk] mask through the dense kernels — every fully-padded K block
# still paid its MXU matmul and its HBM DMA. Here visibility travels as a
# COMPACT PER-ROW DESCRIPTOR instead: segment ids are 1-based, contiguous and
# ascending within a packed row (0 = pad tail), so each query token sees
# exactly one contiguous [start, end) range of K positions — two uint16s,
# packed into one int32 as (start << 16) | end. The descriptor is 2·T bytes
# per row instead of Tq·Tk·4 of bias.
#
# From the descriptor the wrapper derives a per-(q-block, k-block) visibility
# table [B, nq, nk] which rides the scalar-prefetch channel; kernels wrap
# their body in `pl.when(vis > 0)`, so fully-masked K blocks are SKIPPED in
# the fwd grid and in both bwd grids — work scales with real tokens, not
# padding. Skipping is numerically invisible by construction: masked
# probabilities are zeroed exactly (`p = where(mask, p, 0)`), so a processed
# fully-masked block contributes exactly 0 to acc/l and leaves the running
# max untouched — bit-identical to never visiting it (the vis table may even
# be all-ones and nothing changes; tests pin this contract). Fully-masked
# rows produce out = 0, lse = −1e30 and zero gradients. Dropout streams are
# keyed by the logical (b, q-block, k-block) index exactly like the dense
# kernels, so masks are identical regardless of skipping and across fwd/bwd.
#
# In-kernel the element mask needs no K-side array at all: k positions are
# an iota, q rows read their packed range from the descriptor (fed
# lane-replicated [B, Tq, 128] — the same trick the lse/delta residuals use —
# and indexed by `b // nh`, so it is stored once per batch row, not per
# head).
# ---------------------------------------------------------------------------

def _pack_se(q_seg, k_seg):
    """[B, Tq], [B, Tk] segment-id rows (1-based contiguous ascending,
    0 = pad) → packed per-q-row K ranges [B, Tq] int32, (start << 16) | end.
    Pad rows get the empty range [0, 0)."""
    if k_seg.shape[1] >= (1 << 15):
        raise ValueError(
            f"block-sparse flash_attention: Tk={k_seg.shape[1]} overflows "
            f"the 16-bit packed range descriptor")
    q_seg = q_seg.astype(jnp.int32)
    k_seg = k_seg.astype(jnp.int32)
    # pad keys (0) must sort AFTER every real segment id
    kk = jnp.where(k_seg > 0, k_seg, jnp.int32(1 << 30))
    start = jax.vmap(
        lambda a, v: jnp.searchsorted(a, v, side="left"))(kk, q_seg)
    end = jax.vmap(
        lambda a, v: jnp.searchsorted(a, v, side="right"))(kk, q_seg)
    start = jnp.where(q_seg > 0, start, 0).astype(jnp.int32)
    end = jnp.where(q_seg > 0, end, 0).astype(jnp.int32)
    return (start << 16) | end


def _compute_block_vis(se, tq, tk, block_q, block_k, causal):
    """Per-(q-block, k-block) visibility [B, nq, nk] int32 from the packed
    descriptor — conservative: a false-positive visible block is numerically
    invisible (the kernels re-apply the element mask and zero masked
    probabilities), so correctness never depends on this table. Tests
    monkeypatch it to all-ones to pin the skip-is-bitwise-free contract."""
    b = se.shape[0]
    nq, nk = tq // block_q, tk // block_k
    start = se >> 16
    end = se & 0xFFFF
    has = start < end
    sblk = jnp.where(has, start, tk).reshape(b, nq, block_q).min(axis=-1)
    eblk = jnp.where(has, end, 0).reshape(b, nq, block_q).max(axis=-1)
    k0 = jnp.arange(nk, dtype=jnp.int32) * block_k                 # [nk]
    vis = ((sblk[:, :, None] < k0[None, None, :] + block_k)
           & (eblk[:, :, None] > k0[None, None, :]))
    if causal:
        # same block-level test as the dense kernels' causal skip
        q_end = jnp.arange(nq, dtype=jnp.int32) * block_q + block_q - 1
        vis &= k0[None, None, :] <= q_end[None, :, None]
    return vis.astype(jnp.int32)


def _sparse_elem_mask(se_ref, iq, ik, block_q, block_k, causal):
    """[bq, bk] bool element mask from the lane-replicated descriptor
    block (k positions are a global iota — no K-side array)."""
    se = se_ref[0]                                        # [bq, 128] int32
    start = lax.shift_right_logical(se, 16)[:, :1]        # [bq, 1]
    end = (se & 0xFFFF)[:, :1]
    k_pos = ik * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (k_pos >= start) & (k_pos < end)
    if causal:
        q_pos = iq * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask &= q_pos >= k_pos
    return mask


def _fwd_kernel_sparse(seed_ref, vis_ref, q_ref, k_ref, v_ref, se_ref, o_ref,
                       lse_ref, acc_ref, m_ref, l_ref, *, sm_scale, causal,
                       block_q, block_k, dropout_rate, nh):
    b, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        mask = _sparse_elem_mask(se_ref, iq, ik, block_q, block_k, causal)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # zeroing (not just −1e30) keeps masked columns exact even while m
        # is still at its −1e30 init (exp(0) = 1 would otherwise leak) —
        # this is what makes block skipping bit-identical to processing
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref, _block_index(b, iq, ik, nq, nk),
                              (block_q, block_k), dropout_rate)
            p_v = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
        else:
            p_v = p
        v_blk = v_ref[0]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p_v.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(vis_ref[((b // nh) * nq + iq) * nk + ik] > 0)
    def _():
        _body()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l_safe)).astype(jnp.float32)


def _flash_fwd_pallas_sparse(q, k, v, se_rep, vis, nh, sm_scale, causal,
                             block_q, block_k, interpret=False,
                             dropout_rate=0.0, seed=None):
    """q, k, v: [B·nh, Tq/Tk, D] folded; se_rep: [B, Tq, 128]
    lane-replicated packed descriptor; vis: flat [B·nq·nk] int32.
    Returns (out, lse [B·nh, Tq])."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    nq, nk = tq // block_q, tk // block_k
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)

    kernel = functools.partial(_fwd_kernel_sparse, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, dropout_rate=dropout_rate,
                               nh=nh)
    out, lse = kernel_call(
        "flash_fwd_sparse", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, i, j, *_: (b // nh, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, i, j, *_: (b, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seed, vis, q, k, v, se_rep)
    return out, lse[:, :, 0]


def _bwd_dq_kernel_sparse(seed_ref, vis_ref, q_ref, k_ref, v_ref, se_ref,
                          g_ref, lse_ref, delta_ref, dq_ref, dq_acc, *,
                          sm_scale, causal, block_q, block_k, dropout_rate,
                          nh):
    b, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        mask = _sparse_elem_mask(se_ref, iq, ik, block_q, block_k, causal)
        lse = lse_ref[0][:, :1]
        # fully-masked rows have lse = −1e30 → exp(s − lse) would be
        # exp(0) = 1; the zeroing is load-bearing, same as forward
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref, _block_index(b, iq, ik, nq, nk),
                              (block_q, block_k), dropout_rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        delta = delta_ref[0][:, :1]
        ds = p * (dp - delta)
        ds_c = ds.astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds_c, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(vis_ref[((b // nh) * nq + iq) * nk + ik] > 0)
    def _():
        _body()

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_sparse(seed_ref, vis_ref, q_ref, k_ref, v_ref, se_ref,
                           g_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc,
                           dv_acc, *, sm_scale, causal, block_q, block_k,
                           dropout_rate, nh):
    # grid is (bh, nk, nq): k-block outer, q-block inner
    b, ik, iq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        mask = _sparse_elem_mask(se_ref, iq, ik, block_q, block_k, causal)
        lse = lse_ref[0][:, :1]
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # same (b, iq, ik) index as the fwd/dq kernels → identical mask
            keep = _keep_mask(seed_ref, _block_index(b, iq, ik, nq, nk),
                              (block_q, block_k), dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_v = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_v = p
        dv_acc[...] += jax.lax.dot_general(
            p_v.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = delta_ref[0][:, :1]
        ds = p * (dp - delta)
        ds_c = ds.astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds_c, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(vis_ref[((b // nh) * nq + iq) * nk + ik] > 0)
    def _():
        _body()

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas_sparse(q, k, v, se_rep, vis, nh, g, lse, out, sm_scale,
                             causal, block_q, block_k, dropout_rate=0.0,
                             seed=None, interpret=False):
    """Returns (dq, dk, dv); same skip table as forward steers both grids."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    nq, nk = tq // block_q, tk // block_k
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    gf, lse_r, delta_r = _bwd_host_prep(q, g, lse, out)

    dq_kernel = functools.partial(
        _bwd_dq_kernel_sparse, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate, nh=nh)
    dq = kernel_call(
        "flash_bwd_dq_sparse", dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, i, j, *_: (b // nh, i, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, i, j, *_: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i, j, *_: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seed, vis, q, k, v, se_rep, gf, lse_r, delta_r)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel_sparse, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, dropout_rate=dropout_rate, nh=nh)
    dk, dv = kernel_call(
        "flash_bwd_dkv_sparse", dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nk, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, j, i, *_: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, i, *_: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, i, *_: (b, j, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, j, i, *_: (b // nh, i, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, j, i, *_: (b, i, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, j, i, *_: (b, i, 0)),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda b, j, i, *_: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j, i, *_: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, i, *_: (b, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seed, vis, q, k, v, se_rep, gf, lse_r, delta_r)
    return dq, dk, dv


# ---- blockwise JAX path (CPU tests / fallback), same masked math ----------

def _sparse_mask_block(start, end, j0, bk, tq, causal):
    """[BH, Tq, bk] bool mask for the K block at offset j0. start/end:
    [BH, Tq] (per-head-repeated descriptor halves)."""
    k_pos = j0 + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
    mask = ((k_pos[None] >= start[:, :, None])
            & (k_pos[None] < end[:, :, None]))
    if causal:
        q_pos = lax.broadcasted_iota(jnp.int32, (tq, bk), 0)
        mask &= q_pos[None] >= k_pos[None]
    return mask


def _flash_fwd_jax_sparse(q, k, v, start, end, sm_scale, causal, block_k,
                          dropout_rate=0.0, dropout_key=None):
    bh, tq, d = q.shape
    tk = k.shape[1]
    nk = tk // block_k

    def scores(j):
        k_blk = lax.dynamic_slice_in_dim(k, j * block_k, block_k, 1)
        s = jnp.einsum("bqd,bkd->bqk", q, k_blk,
                       preferred_element_type=jnp.float32) * sm_scale
        mask = _sparse_mask_block(start, end, j * block_k, block_k, tq,
                                  causal)
        return jnp.where(mask, s, _NEG_INF), mask

    def pass1(carry, j):
        m, l = carry
        s, mask = scores(j)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l = l * jnp.exp(m - m_new) + jnp.sum(p, -1, keepdims=True)
        return (m_new, l), None

    m0 = jnp.full((bh, tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, tq, 1), jnp.float32)
    (m, l), _ = lax.scan(pass1, (m0, l0), jnp.arange(nk))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    lse = (m + jnp.log(l_safe))[..., 0]

    def pass2(acc, j):
        s, mask = scores(j)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        p = _apply_dropout(p, dropout_rate, dropout_key, j)
        v_blk = lax.dynamic_slice_in_dim(v, j * block_k, block_k, 1)
        acc = acc + jnp.einsum("bqk,bkd->bqd", p.astype(v_blk.dtype), v_blk,
                               preferred_element_type=jnp.float32)
        return acc, None

    out, _ = lax.scan(pass2, jnp.zeros((bh, tq, d), jnp.float32),
                      jnp.arange(nk))
    return out.astype(q.dtype), lse


def _flash_bwd_jax_sparse(res, g, *, sm_scale, causal, block_k,
                          dropout_rate):
    q, k, v, start, end, dropout_key, out, lse = res
    bh, tq, d = q.shape
    tk = k.shape[1]
    nk = tk // block_k
    cdt = q.dtype
    gc = g.astype(cdt)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def step(dq, j):
        j0 = j * block_k
        k_blk = lax.dynamic_slice_in_dim(k, j0, block_k, 1)
        v_blk = lax.dynamic_slice_in_dim(v, j0, block_k, 1)
        s = jnp.einsum("bqd,bkd->bqk", q, k_blk,
                       preferred_element_type=jnp.float32) * sm_scale
        mask = _sparse_mask_block(start, end, j0, block_k, tq, causal)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        p_d = _apply_dropout(p, dropout_rate, dropout_key, j)
        dv_j = jnp.einsum("bqk,bqd->bkd", p_d.astype(cdt), gc,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqd,bkd->bqk", gc, v_blk.astype(cdt),
                        preferred_element_type=jnp.float32)
        if dropout_rate > 0.0 and dropout_key is not None:
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_key, j), 1.0 - dropout_rate,
                p.shape)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta)
        dk_j = jnp.einsum("bqk,bqd->bkd", ds.astype(cdt), q.astype(cdt),
                          preferred_element_type=jnp.float32) * sm_scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds.astype(cdt),
                             k_blk.astype(cdt),
                             preferred_element_type=jnp.float32) * sm_scale
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((bh, tq, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = lax.scan(step, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(bh, tk, d)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(bh, tk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---- dispatch + custom_vjp ------------------------------------------------

def _sparse_pallas_ok(tq: int, tk: int, d: int) -> bool:
    bq, _ = _pick_blocks(tq)
    bk, _ = _pick_blocks(tk)
    return ((_on_tpu() or FORCE_PALLAS_INTERPRET)
            and bq is not None and bk is not None
            and bq >= 64 and bk >= 64 and d % 64 == 0)


def _se_halves_folded(se, nh):
    """Descriptor halves as per-head-repeated [B·nh, Tq] arrays for the jax
    fallback (folded layout is batch-major: index = b·nh + h)."""
    start = jnp.repeat(se >> 16, nh, axis=0)
    end = jnp.repeat(se & 0xFFFF, nh, axis=0)
    return start, end


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_sparse_core(q, k, v, se, dropout_key, nh, sm_scale, causal,
                       dropout_rate):
    out, _ = _flash_sparse_fwd_dispatch(q, k, v, se, dropout_key, nh,
                                        sm_scale, causal, dropout_rate)
    return out


def _flash_sparse_fwd_dispatch(q, k, v, se, dropout_key, nh, sm_scale,
                               causal, dropout_rate):
    tq, d = q.shape[1], q.shape[2]
    tk = k.shape[1]
    bq, _ = _pick_blocks(tq)
    bk, _ = _pick_blocks(tk)
    if _sparse_pallas_ok(tq, tk, d):
        vis = _compute_block_vis(se, tq, tk, bq, bk, causal).reshape(-1)
        se_rep = jnp.broadcast_to(se[:, :, None],
                                  (se.shape[0], tq, _LANES))
        seed = (_seed_from_key(dropout_key) if dropout_rate > 0.0 else None)
        return _flash_fwd_pallas_sparse(
            q, k, v, se_rep, vis, nh, sm_scale, causal, bq, bk,
            interpret=_interpret_arg(dropout_rate),
            dropout_rate=dropout_rate, seed=seed)
    if bk is None:
        raise ValueError(
            f"flash_attention_sparse: seq len {tk} has no power-of-two "
            f"block divisor ≥8; pad the sequence")
    start, end = _se_halves_folded(se, nh)
    key = dropout_key if dropout_rate > 0.0 else None
    return _flash_fwd_jax_sparse(q, k, v, start, end, sm_scale, causal, bk,
                                 dropout_rate, key)


def _flash_sparse_core_fwd(q, k, v, se, dropout_key, nh, sm_scale, causal,
                           dropout_rate):
    out, lse = _flash_sparse_fwd_dispatch(q, k, v, se, dropout_key, nh,
                                          sm_scale, causal, dropout_rate)
    out, lse = _kept(out, KEPT[0]), _kept(lse, KEPT[1])
    key = dropout_key if dropout_rate > 0.0 else None
    return out, (q, k, v, se, key, out, lse)


def _flash_sparse_core_bwd(nh, sm_scale, causal, dropout_rate, res, g):
    q, k, v, se, key, out, lse = res
    tq, d = q.shape[1], q.shape[2]
    tk = k.shape[1]
    bq, _ = _pick_blocks(tq)
    bk, _ = _pick_blocks(tk)
    if _sparse_pallas_ok(tq, tk, d):
        vis = _compute_block_vis(se, tq, tk, bq, bk, causal).reshape(-1)
        se_rep = jnp.broadcast_to(se[:, :, None],
                                  (se.shape[0], tq, _LANES))
        seed = (_seed_from_key(key) if dropout_rate > 0.0 else None)
        dq, dk, dv = _flash_bwd_pallas_sparse(
            q, k, v, se_rep, vis, nh, g, lse, out, sm_scale, causal, bq, bk,
            dropout_rate=dropout_rate, seed=seed,
            interpret=_interpret_arg(dropout_rate))
    else:
        start, end = _se_halves_folded(se, nh)
        dq, dk, dv = _flash_bwd_jax_sparse(
            (q, k, v, start, end, key, out, lse), g, sm_scale=sm_scale,
            causal=causal, block_k=bk, dropout_rate=dropout_rate)
    dse = np.zeros(np.shape(se), jax.dtypes.float0)
    dkey = (None if key is None
            else np.zeros(np.shape(key), jax.dtypes.float0))
    return dq, dk, dv, dse, dkey


_flash_sparse_core.defvjp(_flash_sparse_core_fwd, _flash_sparse_core_bwd)


def flash_attention_packed_sparse(q, k, v, num_heads: int, q_seg, k_seg,
                                  causal: bool = False,
                                  sm_scale: Optional[float] = None,
                                  dropout_rate: float = 0.0,
                                  dropout_key=None, window=None):
    """Block-sparse packed-segment attention on [B, T, H] tensors.

    q_seg/k_seg are the packed segment-id rows (reader.pack_by_tokens
    layout: 1-based contiguous ascending ids, 0 = pad tail) — the dense
    additive [B, 1, Tq, Tk] mask never exists. Supports self attention
    (q_seg is k_seg, optionally causal) and cross attention (Tq ≠ Tk).
    Fully-masked rows (pad queries) return exactly 0. Returns [B, T, H]."""
    b_, tq, hdim = q.shape
    tk = k.shape[1]
    if hdim % num_heads:
        raise ValueError(f"hidden {hdim} not divisible by heads {num_heads}")
    d = hdim // num_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(
            f"flash_attention_sparse: dropout_rate must be in [0, 1), got "
            f"{dropout_rate}")
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError(
            "flash_attention_sparse: dropout_rate > 0 requires a "
            "dropout_key; pass one or set dropout_rate=0 for inference")
    if window is not None:
        raise ValueError(
            "flash_attention_sparse: the block-sparse kernels' per-row "
            "descriptor is a query's segment and holds no window; the dense "
            "flash_attention takes window= (one segment a row)")
    if causal and tq != tk:
        raise ValueError("flash_attention_sparse: causal requires Tq == Tk")
    if v.shape[2] != k.shape[2]:
        raise ValueError(
            f"flash_attention_sparse: the block-sparse kernels know one head "
            f"size for q, k and v; got k {k.shape}, v {v.shape} (the dense "
            f"flash_attention takes a value head size of its own)")
    if q_seg.shape != (b_, tq) or k_seg.shape != (b_, tk):
        raise ValueError(
            f"flash_attention_sparse: seg shapes {q_seg.shape}/"
            f"{k_seg.shape} do not match q/k [{b_}, {tq}]/[{b_}, {tk}]")
    if dropout_rate == 0.0:
        dropout_key = None
    se = _pack_se(q_seg, k_seg)
    qf, kf, vf = (_pack_to_folded(x, num_heads) for x in (q, k, v))
    out = _flash_sparse_core(qf, kf, vf, se, dropout_key, num_heads,
                             float(sm_scale), bool(causal),
                             float(dropout_rate))
    return _folded_to_pack(out, b_)


def flash_attention(q, k, v, bias=None, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    dropout_rate: float = 0.0, dropout_key=None,
                    window: Optional[int] = None):
    """Memory-efficient multi-head attention.

    q: [B, H, T, D]; k: [B, Hkv, T, D], v: [B, Hkv, T, Dv] with Hkv dividing
    H (query head h reads key/value head h // (H / Hkv); Hkv == H is plain
    multi-head attention). The value head size Dv may differ from D, the
    one the scores contract over. bias: additive, broadcastable to
    [B, H, T, T] (e.g. the BERT mask [B,1,1,T]). `sm_scale` defaults to
    D^-1/2. `window` (with `causal`): query i sees keys i - window < j <= i.
    Returns [B, H, T, Dv].
    """
    b, h, t, d = q.shape
    if (h % k.shape[1] or k.shape[3] != d or k.shape[:3] != v.shape[:3]):
        raise ValueError(
            f"flash_attention: the key/value head count must divide the "
            f"query head count, k have q's head size and v k's heads and "
            f"positions; got q {q.shape}, k {k.shape}, v {v.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(
            f"flash_attention: dropout_rate must be in [0, 1), got "
            f"{dropout_rate}")
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError(
            "flash_attention: dropout_rate > 0 requires a dropout_key; "
            "pass one or set dropout_rate=0 for inference")

    fold = lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
    qf, kf, vf = fold(q), fold(k), fold(v)
    bias_f = None
    if bias is not None:
        bias_full = jnp.broadcast_to(bias, (b, h, bias.shape[2], t))
        bias_f = bias_full.reshape(b * h, bias.shape[2], t)
    if dropout_rate == 0.0:
        dropout_key = None  # cotangent structure must match the real usage
    out = _flash_core(qf, kf, vf, bias_f, dropout_key, float(sm_scale),
                      bool(causal), float(dropout_rate),
                      _checked_window(window, causal, t))
    return out.reshape(b, h, t, v.shape[3])
