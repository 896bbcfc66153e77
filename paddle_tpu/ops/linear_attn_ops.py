"""Linear attention with a corrected state: the chunked gated delta rule with
a decay per channel (Kimi Delta Attention; Kimi Linear report,
arXiv:2510.26692, section 3).

No reference analog (barrierye/Paddle predates linear attention). Per head,
with a state S [K, V] (S_0 = 0), a key and query of K channels, a value of V,
a log-decay g_t [K] (<= 0; alpha_t = exp(g_t), one value a CHANNEL) and a
write strength beta_t in [0, 1]:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

that is: decay, then correct the value the key now recalls. With one alpha a
head it is the gated delta rule, with beta k k^T dropped Mamba-2's scan
(`ssm_ops.ssd_scan`, whose chunk is one masked product and needs no inverse).

`gated_delta_rule` computes it in chunks of `chunk` positions. With G_r the
sum of g over the chunk's positions up to r (a vector a position), inside a
chunk

    A_kk[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])      r > i
    A_qk[r, i] = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])      r >= i
    T = (I + Diag(beta) A_kk)^-1 Diag(beta)     block forward substitution,
                                                float32
    W = T (K * exp(G)),   U = T V
    V_new = U - W S,   O = scale (Q * exp(G)) S + scale A_qk V_new
    S <- Diag(exp(G_C)) S + (K * exp(G_C - G))^T V_new

and between chunks the [K, V] state a head, in a `lax.scan`.

**Strong decay.** exp(-G) is never formed: G reaches -100 a chunk at a fresh
draw and float32 ends at e^88. A chunk is cut into sub-blocks of `SUB` = 8
positions. An off-diagonal block (rows in a later sub-block than the columns)
takes both sides' decays relative to the row block's first position s:
exp(G_r - G_s) and exp(G_s - G_i), both exponents <= 0, and is one product on
the matrix unit. A diagonal block takes exp(G_r - G_i), r >= i, channel by
channel ([SUB, SUB, K] differences a sub-block, one fused pass and sum): no
exponent is ever positive, whatever g is.

Decays, beta, the substitution and the states are float32; the matrix
products take their operands in q's dtype (bf16 under AMP) and accumulate in
float32. The op is gray under AMP (not listed in fp16_lists.py), as
`ssd_scan`.

The backward rule is the op's own (`jax.custom_vjp`, inside the registered
op, so that a remat block can hold it): it keeps the op's inputs and the
state entering each chunk, makes the chunk-local terms again, walks the
chunks backwards differentiating one chunk's step at its kept state, and
hands the terms' cotangents back through the terms' own derivative, `ROWS`
chunks-times-heads at a time. A remat block can keep `KEPT` (the rule's
result and the states) so that its forward is not run again.

`kda_rule` is the same rule with what a KDA layer puts around it taken in: q
and k l2-normalised and the log-decay made from the decay gate's raw values,
`-exp(A_log) softplus(raw + dt_bias)`, inside the loop over groups, forward
and backward. At 2 x 8,192 positions and 32 heads of 128 the normalised q and
k, the float32 log-decay and its cotangent are 1 GiB that a layer then never
holds whole (the published kernel, as remembered and not held here, has the
same two switches: `use_qk_l2norm_in_kernel`, `use_gate_in_kernel`).

Two forms compute the same numbers:

- *the kernels* (ops/pallas_kernels/kda_chunk.py): a Pallas forward and a
  Pallas backward kernel that hold a tile of two chunks of a few heads in
  VMEM, make its terms there (the triangles level by level, the inverse by
  block forward substitution as ten masked products), walk the state through
  it and keep the state entering each tile for the backward kernel, which
  differentiates the same tile in VMEM. They run where `pallas_kernels.kda_chunk.takes` says: on a
  TPU backend, for heads of 128 key and 128 value channels, a chunk of 64, T
  whole tiles of 128 and bf16 or float32 operands, and not under a mesh (a
  Mosaic call cannot be partitioned by GSPMD: there the einsum form runs,
  which GSPMD partitions by batch).
- *the einsum form* (here): XLA einsums inside a loop over groups of chunks
  and a loop over a group's chunks. It runs everywhere else (off the TPU,
  under a mesh, for the shapes the kernels do not take), and it is the
  kernels' oracle in the tests.

The shapes and the backend choose (`rule_path`); no flag, attribute or
argument does. The counter `ops/kda_lowered{path="pallas"|"einsum"}` says
which form an op was lowered to. The kernels are imported where an op is
lowered, never by `import paddle_tpu`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from ..core.remat import kept

_HI = lax.Precision.HIGHEST
SUB = 8                 # positions a sub-block: see the module docstring
BLOCK = 16              # rows a diagonal block of the substitution
ROWS = 512              # chunks x heads a group: `_groups`
KEPT = ("gated_delta_rule/out", "gated_delta_rule/states")


def _heads_first(x):
    """[B, T, H, D] -> [B, H, T, D]: once a layer, at full size. (Cutting a
    group's chunks out of [B, T, H, D] moves the heads across the positions
    group by group: a fifth of the forward pass on a v5e.)"""
    return jnp.swapaxes(x, 1, 2)


def _to_chunks(x, chunk):
    """[B, H, T', D] -> [T' / chunk, B, H, chunk, D]."""
    b, h, t, d = x.shape
    return x.reshape(b, h, t // chunk, chunk, d).transpose(2, 0, 1, 3, 4)


def _from_chunks(x):
    """[n, B, H, C, D] -> [B, H, n * C, D]."""
    n, b, h, c, d = x.shape
    return x.transpose(1, 2, 0, 3, 4).reshape(b, h, n * c, d)


def _decayed_products(q, k, cum):
    """The chunk-local decayed products of q's and of k's rows with k's
    columns, [.., C, C] each, float32, lower-triangular (the diagonal
    included): out[r, i] = sum_c x_r[c] k_i[c] exp(cum_r[c] - cum_i[c]),
    r >= i. q, k [.., C, K]; cum [.., C, K] float32, not increasing along C.
    No exponent is positive (the module docstring has the scheme)."""
    lead, (c, dk) = k.shape[:-2], k.shape[-2:]
    s, lo, f32 = c // SUB, k.dtype, jnp.float32
    sub = lambda x: x.reshape(lead + (s, SUB, dk))
    cums, qs, ks = sub(cum), sub(q), sub(k)
    first = cums[..., :1, :]                            # [.., s, 1, K]
    rel = jnp.exp(cums - first)
    rows_rel = [(x.astype(f32) * rel).astype(lo) for x in (qs, ks)]

    # diagonal blocks, channel by channel: [.., s, SUB (r), SUB (i), K] of
    # exp(cum_r - cum_i), r >= i, a group's worth at a time
    qf, kf = qs.astype(f32), ks.astype(f32)
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))[:, :, None]
    weight = kf[..., None, :, :] * jnp.exp(jnp.where(
        lower, cums[..., :, None, :] - cums[..., None, :, :], -jnp.inf))
    diag = [jnp.sum(x[..., :, None, :] * weight, axis=-1) for x in (qf, kf)]

    # off-diagonal blocks: row block a over the a sub-blocks before it
    blocks = ([], [])
    for a in range(s):
        before = a and (ks[..., :a, :, :].astype(f32) * jnp.exp(
            first[..., a:a + 1, :, :] - cums[..., :a, :, :])).astype(lo)
        for rows, own, out in zip(rows_rel, diag, blocks):
            row = [jnp.zeros(lead + (SUB, SUB), f32)] * s
            row[a] = own[..., a, :, :]
            if a:
                off = jnp.einsum("...rk,...bik->...rbi", rows[..., a, :, :],
                                 before, preferred_element_type=f32)
                row[:a] = [off[..., b, :] for b in range(a)]
            out.append(jnp.concatenate(row, axis=-1))      # [.., SUB, C]
    return tuple(jnp.stack(rows, axis=-3).reshape(lead + (c, c))
                 for rows in blocks)


def _prepared(q, k, g, gate, l2_eps):
    """The rule's q, k and log-decay from what the op was handed, for the
    chunks given ([n, B, H, C, K]). With `l2_eps`, q and k are divided by
    max(their norm, l2_eps) a head and position, in float32, and rounded to
    their dtype; with `gate` = (A_log [H], dt_bias [H, K]), g holds the decay
    gate's raw values and the log-decay is
    -exp(A_log) softplus(g + dt_bias), float32."""
    f32 = jnp.float32
    if l2_eps:
        def unit(x):
            xf = x.astype(f32)
            norm = jnp.sqrt(jnp.sum(xf * xf, axis=-1, keepdims=True))
            return (xf / jnp.maximum(norm, l2_eps)).astype(x.dtype)
        q, k = unit(q), unit(k)
    g = g.astype(f32)
    if gate is not None:
        a_log, dt_bias = gate
        g = (-jnp.exp(a_log.astype(f32))[:, None, None]
             * jax.nn.softplus(g + dt_bias.astype(f32)[:, None, :]))
    return q, k, g


def _solve_unit_lower(system, rhs):
    """system^-1 rhs for unit lower-triangular systems [.., C, C] and rhs
    [.., C, N], float32. Forward substitution row by row is C steps that each
    wait for the last (`lax.linalg.triangular_solve` alone: 1.6 ms for 512
    systems of 64 on a v5e, three fifths of a group's forward pass); here it
    runs on the `BLOCK` x `BLOCK` diagonal blocks alone, all at once (their
    inverses), and the blocks' rows are then substituted block by block with
    products at full precision."""
    lead, c = system.shape[:-2], system.shape[-1]
    nb, f32 = c // BLOCK, jnp.float32
    at = lambda a: slice(a * BLOCK, (a + 1) * BLOCK)
    diag = jnp.stack([system[..., at(a), at(a)] for a in range(nb)], axis=-3)
    inverse = lax.linalg.triangular_solve(
        diag, jnp.broadcast_to(jnp.eye(BLOCK, dtype=f32), diag.shape),
        left_side=True, lower=True, unit_diagonal=True)
    out = None
    for a in range(nb):
        rows = rhs[..., at(a), :]
        if a:
            rows = rows - jnp.matmul(system[..., at(a), :a * BLOCK], out,
                                     precision=_HI)
        rows = jnp.matmul(inverse[..., a, :, :], rows, precision=_HI)
        out = rows if out is None else jnp.concatenate([out, rows], axis=-2)
    return out


def _chunk_terms(q, k, v, g, beta, gate, scale, l2_eps):
    """What a chunk's step needs of its own positions, for the chunks given,
    [n, B, H, C, .]: (W, U, the decayed scaled queries, the scaled A_qk, the
    keys decayed to the chunk's end, the whole chunk's decay [n, B, H, K]),
    and the most negative cumulative log-decay among them. q, k, v in the
    operands' dtype, beta [n, B, H, C, 1] float32, g and `gate` as
    `_prepared` reads them. U and the decay float32, the products' operands
    in q's dtype."""
    lo, f32 = q.dtype, jnp.float32
    q, k, g = _prepared(q, k, g, gate, l2_eps)
    cum = jnp.cumsum(g, axis=-2)
    a_qk, a_kk = _decayed_products(q, k, cum)
    # [W | U] = (I + Diag(beta) A_kk)^-1 Diag(beta) [K * exp(G) | V], in
    # float32 at full precision (the inverse's entries cancel: with its
    # products' operands rounded to bf16 the op's error doubles, and they
    # are 2% of its operations)
    down = jnp.exp(cum)
    kf = k.astype(f32)
    system = jnp.eye(k.shape[-2], dtype=f32) + beta * jnp.tril(a_kk, -1)
    wu = _solve_unit_lower(system, beta * jnp.concatenate(
        [kf * down, v.astype(f32)], axis=-1))
    w, u = wu[..., :k.shape[-1]], wu[..., k.shape[-1]:]
    q_down = q.astype(f32) * down * scale
    k_end = kf * jnp.exp(cum[..., -1:, :] - cum)
    terms = (w.astype(lo), u, q_down.astype(lo), (a_qk * scale).astype(lo),
             k_end.astype(lo), jnp.exp(cum[..., -1, :]))
    return terms, lax.stop_gradient(jnp.min(cum[..., -1, :]))


def _groups(t, b, h, chunk):
    """In how many groups of whole chunks a sequence of T positions is
    taken, `ROWS` chunks-times-heads a group: the chunk-local terms of a
    whole layer at once are a dozen float32 arrays of the size of g, and a
    group's are what the chip holds while it makes them."""
    nc = t // chunk
    groups = max(1, min(nc, nc * b * h // ROWS))
    while nc % groups:
        groups -= 1
    return groups


def _group_inputs(ins, i, span, chunk):
    """Group i's `span` positions of the op's inputs (heads first:
    [B, H, T, .]), a chunk at a time: [n, B, H, C, .]."""
    return tuple(_to_chunks(lax.dynamic_slice_in_dim(x, i * span, span,
                                                     axis=2), chunk)
                 for x in ins)


def _chunk_step(state, terms):
    """One chunk: the state entering it [B, H, K, V] float32 and its terms
    -> (the state leaving it, its outputs [B, H, C, V] in the operands'
    dtype)."""
    w, u, q_down, a_qk, k_end, decay = terms
    lo, f32 = w.dtype, jnp.float32
    s = state.astype(lo)
    v_new = u - jnp.einsum("bhck,bhkv->bhcv", w, s,
                           preferred_element_type=f32)
    vl = v_new.astype(lo)
    out = (jnp.einsum("bhck,bhkv->bhcv", q_down, s,
                      preferred_element_type=f32)
           + jnp.einsum("bhcs,bhsv->bhcv", a_qk, vl,
                        preferred_element_type=f32))
    state = decay[..., None] * state + jnp.einsum(
        "bhck,bhcv->bhkv", k_end, vl, preferred_element_type=f32)
    return state, out.astype(lo)


def _rule_states(q, k, v, g, beta, gate, chunk, scale, l2_eps):
    """(o [B, T, H, V] in q's dtype, the decay floor, the inputs heads first,
    the state entering each chunk [NC, B, H, K, V] float32): a loop over
    groups of chunks (their terms), inside it the loop over a group's chunks
    (the state)."""
    ins = (*map(_heads_first, (q, k, v, g)),
           _heads_first(beta[..., None]))
    b, t, h, dk = k.shape
    groups = _groups(t, b, h, chunk)

    def step(state, chunk_terms):
        after, out = _chunk_step(state, chunk_terms)
        return after, (out, state)

    def group(state, i):
        terms, floor = _chunk_terms(
            *_group_inputs(ins, i, t // groups, chunk), gate, scale, l2_eps)
        state, (out, states) = lax.scan(step, state, terms)
        return state, (_from_chunks(out), floor, states)

    zero = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, (out, floors, states) = lax.scan(group, zero, jnp.arange(groups))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, t, -1)
    return (_heads_first(out), jnp.min(floors), ins,
            states.reshape((-1,) + states.shape[2:]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kda_rule(q, k, v, g, beta, gate, chunk, scale, l2_eps):
    return _rule_states(q, k, v, g, beta, gate, chunk, scale, l2_eps)[:2]


def rule_path(q, v, chunk: int, under_mesh: bool = False) -> str:
    """"pallas" where the kernels take a rule over q [B, T, H, K] and v
    [B, T, H, V] at this chunk, else "einsum" (the module docstring has the
    rule)."""
    from .pallas_kernels import kda_chunk as kernels
    return ("pallas" if not under_mesh and kernels.takes(
        q.shape[1], q.shape[3], v.shape[3], chunk, q.dtype) else "einsum")


def kda_rule(q, k, v, g, beta, gate, chunk, scale, l2_eps,
             under_mesh: bool = False):
    """The chunked rule with what a KDA layer puts around it taken in: q and
    k l2-normalised inside where `l2_eps` is not 0, and with `gate` =
    (A_log [H], dt_bias [H, K]) the log-decay made inside from the decay
    gate's raw values g (`_prepared`); `gate` None: g is the log-decay.
    Returns (o [B, T, H, V] in q's dtype, the decay floor: the most negative
    cumulative log-decay a chunk reaches, float32, no gradient), by the
    kernels where `rule_path` says so and by the einsum form elsewhere
    (`under_mesh`: whether the op is lowered under a mesh). Inside, a tile or
    a group of chunks at a time: the normalised q and k and the float32
    log-decay of a whole layer are never held."""
    k, v, beta = k.astype(q.dtype), v.astype(q.dtype), beta.astype(jnp.float32)
    if rule_path(q, v, chunk, under_mesh) == "pallas":
        from .pallas_kernels import kda_chunk as kernels
        return kernels.kda_rule(q, k, v, g, beta, gate, scale, l2_eps)
    return _kda_rule(q, k, v, g, beta, gate, chunk, scale, l2_eps)


def gated_delta_rule(q, k, v, g, beta, chunk=64, scale=None):
    """The chunked gated delta rule (the module docstring has the
    equations). q, k [B, T, H, K]; v [B, T, H, V]; g [B, T, H, K] the
    log-decay a channel (<= 0); beta [B, T, H]. T whole chunks, `chunk` a
    multiple of 16. `scale` None: K^-1/2. Returns o [B, T, H, V] in q's
    dtype."""
    return kda_rule(q, k, v, g, beta, None, chunk, _scale(scale, k), 0.0)[0]


def _scale(scale, k):
    return k.shape[-1] ** -0.5 if scale is None else scale


def _rule_fwd(q, k, v, g, beta, gate, chunk, scale, l2_eps):
    out, floor, ins, states = _rule_states(q, k, v, g, beta, gate, chunk,
                                           scale, l2_eps)
    out, states = kept(out, KEPT[0]), kept(states, KEPT[1])
    return (out, floor), (ins, gate, states)


def _rule_bwd(chunk, scale, l2_eps, res, cotangents):
    ins, gate, states = res                # the inputs heads first
    d_out = _heads_first(cotangents[0])    # the floor carries no gradient
    b, h, t, _ = ins[1].shape
    groups = _groups(t, b, h, chunk)
    span = t // groups

    def terms_of(group_ins, gate):
        return _chunk_terms(*group_ins, gate, scale, l2_eps)

    def back(d_state, item):
        state, chunk_terms, d_o = item
        _, step_vjp = jax.vjp(_chunk_step, state, chunk_terms)
        return step_vjp((d_state, d_o))

    # the groups backwards: a group's terms are made again with what their
    # own derivative needs of them, its chunks walked backwards from the
    # state's cotangent the later group left, the terms' cotangents handed
    # back to the group's positions of the inputs, which are written in
    # place
    def group(carry, item):
        d_state, d_ins, d_gate = carry
        i, entering = item
        terms, terms_vjp, _ = jax.vjp(
            terms_of, _group_inputs(ins, i, span, chunk), gate, has_aux=True)
        d_o = _group_inputs((d_out.astype(terms[0].dtype),), i, span, chunk)[0]
        d_state, d_terms = lax.scan(back, d_state, (entering, terms, d_o),
                                    reverse=True)
        d_group, d_gate_i = terms_vjp(d_terms)
        d_ins = tuple(
            lax.dynamic_update_slice_in_dim(whole, _from_chunks(part),
                                            i * span, axis=2)
            for whole, part in zip(d_ins, d_group))
        d_gate = jax.tree_util.tree_map(jnp.add, d_gate, d_gate_i)
        return (d_state, d_ins, d_gate), None

    zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
    (_, d_ins, d_gate), _ = lax.scan(
        group, (jnp.zeros_like(states[0]), zeros(ins), zeros(gate)),
        (jnp.arange(groups), states.reshape((groups, -1) + states.shape[1:])),
        reverse=True)
    dq, dk, dv, dg, dbeta = map(_heads_first, d_ins)
    return dq, dk, dv, dg, dbeta[..., 0], d_gate


_kda_rule.defvjp(_rule_fwd, _rule_bwd)


@register_op("gated_delta_rule")
def _gated_delta_rule(ctx, inputs, attrs):
    """Q, K [B, T, H, K], V [B, T, H, V], Beta [B, T, H], and G
    [B, T, H, K]: the log-decay (<= 0), or, with ALog [H] and DtBias [H * K]
    given, the decay gate's raw values (the log-decay is then
    -exp(ALog) softplus(G + DtBias), made inside). `qk_l2norm` (an epsilon,
    0: off) divides q and k by max(their norm, epsilon) inside. Out
    [B, T, H, V] in Q's dtype; DecayFloor [] float32, the most negative
    cumulative log-decay any chunk reaches (no gradient): how far the
    sub-block scheme is from float32's range."""
    from ..observability import get_registry
    from .common import opt_input
    from .fused_ops import _under_mesh

    (q,), (k,), (v,) = inputs["Q"], inputs["K"], inputs["V"]
    (g,), (beta,) = inputs["G"], inputs["Beta"]
    a_log, dt_bias = opt_input(inputs, "ALog"), opt_input(inputs, "DtBias")
    chunk = int(attrs.get("chunk", 64))
    if q.shape[1] % chunk or chunk % BLOCK or BLOCK % SUB:
        raise ValueError(
            f"gated_delta_rule: sequence length {q.shape[1]} is not whole "
            f"chunks of {chunk}, or the chunk no multiple of {BLOCK}")
    under_mesh = _under_mesh(ctx)
    get_registry().counter(
        "ops/kda_lowered", path=rule_path(q, v, chunk, under_mesh)).inc()
    gate = None if a_log is None else (
        a_log, dt_bias.reshape(k.shape[2], k.shape[3]))
    out, floor = kda_rule(q, k, v, g, beta, gate, chunk, _scale(None, k),
                          float(attrs.get("qk_l2norm", 0.0)), under_mesh)
    return {"Out": [out], "DecayFloor": [floor]}
