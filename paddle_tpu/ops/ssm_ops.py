"""State-space (Mamba-2) ops: the causal depthwise convolution and the chunked
state-space-dual scan.

No reference analog (barrierye/Paddle predates state-space layers). The
recurrence, per head h with state size N and head dim P (Dao & Gu 2024,
"Transformers are SSMs", section 6):

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T        S: [P, N]
    y_t = S_t C_t + D * x_t

`ssd_scan` computes it in chunks of `chunk` positions: inside a chunk as a
masked [chunk, chunk] product (the dual, attention-like form), between chunks
through the chunk states; nothing of size [T, T]. Decays, dt and the states
are float32; the products take their operands in x's dtype (bf16 under AMP)
and accumulate in float32. Two forms compute the same numbers:

- *the kernels* (ops/pallas_kernels/ssd_scan.py): a Pallas forward and a
  Pallas backward kernel that carry the state from chunk to chunk in VMEM
  and keep each head's [chunk, chunk] decays and weights there. They run
  where `pallas_kernels.ssd_scan.takes` says: on a TPU backend, with
  `chunk`, N and a group's R·P multiples of 128, a head size P that
  divides 128, at most 40 heads a group, and T whole chunks. Under a mesh
  the op runs them per data shard (a Mosaic call cannot be partitioned by
  GSPMD).
- *the einsum form* (`_ssd`, here): whole-array einsums, the chunk states
  combined by one [chunks, chunks] decay matrix, no loop. Its backward
  (`custom_vjp`) keeps the op's inputs only and recomputes the chunk-local
  terms and the states. It runs everywhere else (off the TPU, and for the
  shapes the kernels do not take), and it is the kernels' oracle in the
  tests.

The shapes and the backend choose; no flag, attribute or argument does. The
counter `ops/ssd_scan_lowered{path="pallas"|"einsum"}` says which form an op
was lowered to.

Both ops are gray under AMP (not listed in fp16_lists.py): they take the
activations in the dtype they arrive in and keep what is sensitive in float32
themselves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import act_map, one, opt_input


@register_op("causal_conv1d")
def _causal_conv1d(ctx, inputs, attrs):
    """Depthwise causal convolution along time: X [B, T, C], Filter [C, K],
    Bias [C] (optional): y[t, c] = act(b[c] + Σ_j w[c, j] * x[t - (K-1) + j, c])
    with x before the sequence's start taken as zero. K shifted products
    summed in float32; the result has X's dtype."""
    (x,) = inputs["X"]
    (w,) = inputs["Filter"]
    b = opt_input(inputs, "Bias")
    k, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xp[:, j:j + t].astype(jnp.float32) * wf[:, j] for j in range(k))
    if b is not None:
        y = y + b.astype(jnp.float32)
    return one(act_map()[attrs.get("activation", "")](y).astype(x.dtype))


def _segsum(a):
    """a [..., L] -> [..., L, L] with out[i, j] = Σ_{j < m <= i} a[m] for
    j <= i and -inf above the diagonal (so that exp gives the decay from
    position j to position i, 0 where j is later)."""
    n = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    keep = jnp.tril(jnp.ones((n, n), bool))
    return jnp.where(keep, diff, -jnp.inf)


def _ssd(x, dt, a, b, c, chunk):
    """x [B, T, H, P]; dt [B, T, H] float32 (> 0); a [H] float32 (< 0);
    b, c [B, T, G, N] with H a multiple of G. Returns y [B, T, H, P]
    float32, without the D skip."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc, lo = t // chunk, x.dtype
    f32 = jnp.float32
    xc = x.reshape(bsz, nc, chunk, g, h // g, p)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    dtc = dt.reshape(bsz, nc, chunk, g, h // g)
    da = dtc * a.reshape(g, h // g)                   # log-decay a position
    da = jnp.moveaxis(da, 2, -1)                      # [B, nc, G, R, L]
    cum = jnp.cumsum(da, axis=-1)

    # inside a chunk: (C B^T ∘ decay ∘ dt) x
    cb = jnp.einsum("bzlgn,bzsgn->bzgls", cc, bc, preferred_element_type=f32)
    decay = jnp.exp(_segsum(da))                      # [B, nc, G, R, L, L]
    w = cb[:, :, :, None] * decay * jnp.moveaxis(dtc, 2, -1)[..., None, :]
    y_in = jnp.einsum("bzgrls,bzsgrp->bzlgrp", w.astype(lo), xc,
                      preferred_element_type=f32)

    # each chunk's own state at its end: Σ_s decay(s -> end) dt_s x_s B_s^T
    to_end = jnp.exp(cum[..., -1:] - cum) * jnp.moveaxis(dtc, 2, -1)
    xw = xc * jnp.moveaxis(to_end, -1, 2)[..., None].astype(lo)
    states = jnp.einsum("bzsgrp,bzsgn->bzgrpn", xw, bc,
                        preferred_element_type=f32)

    # the state entering each chunk: the earlier chunks' states, decayed
    # over the chunks between (one [nc, nc] strictly-lower matrix)
    between = _between_chunks(cum[..., -1])           # [B, G, R, nc, nc]
    entering = jnp.einsum("bgrzy,bygrpn->bzgrpn", between, states,
                          preferred_element_type=f32)

    # what the entering state gives each position: C_l · S, decayed to l
    y_off = jnp.einsum("bzlgn,bzgrpn->bzlgrp", cc.astype(f32), entering,
                       preferred_element_type=f32)
    y_off = y_off * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return (y_in + y_off).reshape(bsz, t, h, p)


def _between_chunks(total):
    """total [B, nc, G, R]: each chunk's whole log-decay. Returns
    [B, G, R, nc, nc] with out[z, y] = exp(Σ_{y < m < z} total[m]) for y < z
    (the decay a state made at the end of chunk y suffers before chunk z
    begins) and 0 elsewhere."""
    tt = jnp.moveaxis(total, 1, -1)                   # [B, G, R, nc]
    nc = tt.shape[-1]
    cs = jnp.cumsum(tt, axis=-1)
    # Σ_{y < m < z} = cs[z-1] - cs[y]
    prev = jnp.pad(cs, ((0, 0),) * 3 + ((1, 0),))[..., :-1]   # cs[z-1]
    diff = prev[..., :, None] - cs[..., None, :]
    keep = jnp.tril(jnp.ones((nc, nc), bool), -1)
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def _ssd_skip(x, dt, a, b, c, d, chunk):
    """The einsum form whole: the scan, + d ∘ x, in x's dtype."""
    y = _ssd(x, dt, a, b, c, chunk)
    y = y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_scan_einsum(x, dt, a, b, c, d, chunk):
    """`ssd_scan` by the einsum form, whatever the backend and the shapes."""
    return _ssd_skip(x, dt, a, b, c, d, chunk)


def _ssd_fwd(x, dt, a, b, c, d, chunk):
    return _ssd_skip(x, dt, a, b, c, d, chunk), (x, dt, a, b, c, d)


def _ssd_bwd(chunk, res, g):
    # the inputs are all that is kept: the chunk-local products and the
    # chunk states are made again here, then differentiated
    _, vjp = jax.vjp(functools.partial(_ssd_skip, chunk=chunk), *res)
    return vjp(g)


ssd_scan_einsum.defvjp(_ssd_fwd, _ssd_bwd)


def scan_path(t: int, h: int, p: int, g: int, n: int, chunk: int) -> str:
    """"pallas" where the kernels take a scan of T positions, H heads of P
    in G groups and a state of N, else "einsum" (the module docstring has
    the rule)."""
    from .pallas_kernels import ssd_scan as kernels
    return "pallas" if kernels.takes(t, h, p, g, n, chunk) else "einsum"


def ssd_scan(x, dt, a, b, c, d, chunk):
    """The chunked state-space-dual scan with its skip (see the module
    docstring). x [B, T, H, P]; dt [B, T, H] float32 (> 0); a [H] float32
    (< 0); b, c [B, T, G, N] with H a multiple of G; d [H]. Returns
    [B, T, H, P] in x's dtype, by the kernels where they take the shapes and
    by the einsum form elsewhere."""
    from .pallas_kernels import ssd_scan as kernels
    if kernels.takes(*x.shape[1:], *b.shape[2:], chunk):
        return kernels.ssd_scan(x, dt, a, b, c, d, chunk)
    return ssd_scan_einsum(x, dt, a, b, c, d, chunk)


@register_op("ssd_scan")
def _ssd_scan(ctx, inputs, attrs):
    """Mamba-2's mixer core. X [B, T, H*P], Dt [B, T, H] (raw), ALog [H],
    B, C [B, T, G*N], D [H], DtBias [H]:
    dt = softplus(Dt + DtBias), A = -exp(ALog), the scan, + D * x. T must be
    a multiple of `chunk`. Out [B, T, H*P] in X's dtype. Under a mesh the
    kernels run on each data shard's own sequences."""
    from ..observability import get_registry
    from .fused_ops import _per_data_shard, _under_mesh

    (x,) = inputs["X"]
    (dt,) = inputs["Dt"]
    (a_log,) = inputs["ALog"]
    (b,) = inputs["B"]
    (c,) = inputs["C"]
    (d,) = inputs["D"]
    (dt_bias,) = inputs["DtBias"]
    h, g = int(attrs["num_heads"]), int(attrs["n_groups"])
    chunk = int(attrs.get("chunk", 128))
    if x.shape[1] % chunk:
        raise ValueError(f"ssd_scan: sequence length {x.shape[1]} is not a "
                         f"multiple of the chunk {chunk}")
    f32 = jnp.float32
    path = scan_path(x.shape[1], h, x.shape[2] // h, g, b.shape[2] // g,
                     chunk)

    def scan(x, dt, b, c, a_log, d, dt_bias, key=None):
        bsz, t = x.shape[0], x.shape[1]
        get_registry().counter("ops/ssd_scan_lowered", path=path).inc()
        dtf = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        y = ssd_scan(x.reshape(bsz, t, h, -1), dtf,
                     -jnp.exp(a_log.astype(f32)),
                     b.reshape(bsz, t, g, -1).astype(x.dtype),
                     c.reshape(bsz, t, g, -1).astype(x.dtype),
                     d.astype(f32), chunk)
        return y.reshape(x.shape)

    if path == "pallas" and _under_mesh(ctx):
        return one(_per_data_shard(ctx, scan, (x, dt, b, c), None,
                                   replicated=(a_log, d, dt_bias)))
    return one(scan(x, dt, b, c, a_log, d, dt_bias))
