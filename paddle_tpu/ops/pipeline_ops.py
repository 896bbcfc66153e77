"""Program-level pipeline-parallel op.

Reference analog: ``python/paddle/fluid/optimizer.py:2677`` PipelineOptimizer
(cuts a user program into sections) executed by PipelineTrainer/SectionWorker
(section_worker.cc:141 — scopes flowing through CPU queues between devices).

TPU-native redesign: the cut stages must be *isomorphic* (the transformer
per-layer case); one template sub-block is kept and its parameters are
stage-stacked, then the whole GPipe schedule (parallel/pipeline.py —
lax.scan over ppermute ring) compiles into the one jitted step and is
differentiable end-to-end, so the backward pipeline and the per-stage
parameter gradients fall out of the vjp tape. Without a `pp` mesh axis the
op degrades to a sequential loop over stages (same math, no pipelining).
"""
from __future__ import annotations

import logging

import jax.numpy as jnp

from ..core.registry import register_op

logger = logging.getLogger(__name__)


def _log_schedule(kind, n, m):
    """Trace-time schedule report: GPipe ticks and bubble fraction (every
    tick runs one full stage on every device, so idle fraction =
    (n-1)/(m+n-1))."""
    ticks = m + n - 1
    logger.info("[pipeline] %s schedule: stages=%d microbatches=%d "
                "ticks=%d bubble_fraction=%.3f", kind, n, m, ticks,
                (n - 1) / ticks if ticks else 0.0)


@register_op("pipeline")
def _pipeline(ctx, inputs, attrs):
    from ..core.executor import ExecContext, _run_block

    (x,) = inputs["X"]
    flat_params = inputs["Params"]          # [stage-major][param]
    n_stages = attrs["n_stages"]
    n_params = attrs["n_params"]
    m = attrs.get("num_microbatches", 1)
    axis = attrs.get("axis", "pp")
    data_axis = attrs.get("data_axis")
    block = attrs["sub_block"]
    in_name = attrs["in_name"]
    out_name = attrs["out_name"]
    param_names = attrs["param_names"]      # template (stage-0) param names
    capture_names = attrs.get("capture_names", [])
    captures = inputs.get("Captures", [])
    b = x.shape[0]

    # captures with a leading batch dim (attention masks etc.) must be
    # microbatched and travel WITH the activation through the ring — at any
    # tick each stage holds a DIFFERENT microbatch; batch-free captures
    # (scalars, tables) are safely closed over. capture_spec overrides the
    # shape heuristic for ambiguous cases (a [T,...] table with T == batch).
    spec = attrs.get("capture_spec") or {}

    def _is_batched(name, c):
        if name in spec:
            return spec[name] == "batched"
        return getattr(c, "ndim", 0) >= 1 and c.shape[0] == b

    batched = [i for i, c in enumerate(captures)
               if _is_batched(capture_names[i], c)]
    static = {capture_names[i]: captures[i]
              for i in range(len(captures)) if i not in batched}
    bc_names = [capture_names[i] for i in batched]

    # one subkey per step from the threaded stream; stages fold in their
    # stage index so dropout masks differ per stage AND advance per step.
    # Each microbatch additionally carries its OWN key through the ring
    # (raw key data rides the payload like a batched capture), so masks
    # differ per (stage, microbatch) — ADVICE r3.
    import jax as _jax
    from jax import lax as _lax
    base_key = ctx.rng() if not ctx.is_test else None

    def stage_fn(params_list, payload, stage_key=None):
        inp, *bcaps = payload
        env = dict(zip(param_names, params_list))
        env.update(static)
        env.update(zip(bc_names, bcaps))
        env[in_name] = inp
        sub = ExecContext(stage_key, is_test=ctx.is_test, mesh=ctx.mesh,
                          amp=ctx.amp, data_axis=ctx.data_axis)
        _run_block(block, env, sub)
        return (env[out_name], *bcaps)

    mesh = ctx.mesh
    if mesh is None or axis not in mesh.axis_names:
        # no pp axis: sequential stages (identical math, no overlap)
        payload = (x, *[captures[i] for i in batched])
        for s in range(n_stages):
            sk = (None if base_key is None
                  else _jax.random.fold_in(base_key, s))
            payload = stage_fn(
                flat_params[s * n_params:(s + 1) * n_params], payload, sk)
        return {"Out": [payload[0]]}

    if base_key is not None:
        _typed = _jax.dtypes.issubdtype(getattr(base_key, "dtype", None),
                                        _jax.dtypes.prng_key)
        _impl = str(_jax.random.key_impl(base_key)) if _typed else None
        _mkeys = _jax.random.split(base_key, m)
        _mdata = _jax.random.key_data(_mkeys) if _typed else _mkeys

    def staged_fn(params_list, payload):
        if base_key is None:
            return stage_fn(params_list, payload, None)
        # last payload element = this microbatch's raw key data; wrap it,
        # fold in the stage index, and pass the data through unchanged so
        # the NEXT stage sees the same microbatch key after the ppermute
        inp_caps, kd = payload[:-1], payload[-1]
        mk = (_jax.random.wrap_key_data(kd, impl=_impl) if _impl else kd)
        sk = _jax.random.fold_in(mk, _lax.axis_index(axis))
        out = stage_fn(params_list, inp_caps, sk)
        return (*out, kd)

    from ..parallel.pipeline import pipeline_step

    stacked = [jnp.stack([flat_params[s * n_params + j]
                          for s in range(n_stages)])
               for j in range(n_params)]
    if b % m:
        raise ValueError(f"pipeline: batch {b} not divisible by "
                         f"num_microbatches {m}")

    def micro(a):
        return a.reshape((m, b // m) + a.shape[1:])

    xs = (micro(x), *[micro(captures[i]) for i in batched])
    if base_key is not None:
        xs = xs + (_mdata,)
    _log_schedule("GPipe", n_stages, m)
    out = pipeline_step(staged_fn, stacked, xs, mesh, axis,
                        data_axis=data_axis)
    return {"Out": [out.reshape(x.shape)]}


@register_op("pipeline_hetero")
def _pipeline_hetero(ctx, inputs, attrs):
    """Heterogeneous pipeline: per-stage sub-blocks with their own ops,
    params, captures, and boundary shapes (reference heterogeneous sections,
    section_worker.cc:141) — lowered to the lax.switch ppermute ring in
    parallel/pipeline.pipeline_hetero, or a sequential stage loop without a
    `pp` mesh axis."""
    import jax as _jax

    from ..core.executor import ExecContext, _run_block

    (x,) = inputs["X"]
    flat_params = inputs["Params"]
    flat_caps = inputs.get("Captures", [])
    blocks = attrs["sub_blocks"]
    names = attrs["boundary_names"]
    param_names = attrs["param_names"]      # list of per-stage name lists
    cap_names = attrs["capture_names"]
    n_stages = attrs["n_stages"]
    m = attrs.get("num_microbatches", 1)
    axis = attrs.get("axis", "pp")
    spec = attrs.get("capture_spec") or {}
    b = x.shape[0]

    # split the flat input lists back per stage
    ps, cs, pi, ci = [], [], 0, 0
    for k in range(n_stages):
        ps.append(list(flat_params[pi:pi + len(param_names[k])]))
        cs.append(list(flat_caps[ci:ci + len(cap_names[k])]))
        pi += len(param_names[k])
        ci += len(cap_names[k])

    def _is_batched(name, c):
        if name in spec:
            return spec[name] == "batched"
        return getattr(c, "ndim", 0) >= 1 and c.shape[0] == b

    base_key = ctx.rng() if not ctx.is_test else None

    def make_stage(k, micro_caps: bool):
        bnames = [n for n, c in zip(cap_names[k], cs[k]) if _is_batched(n, c)]
        static = {n: c for n, c in zip(cap_names[k], cs[k])
                  if n not in bnames}
        key_k = (None if base_key is None
                 else _jax.random.fold_in(base_key, k))
        # ADVICE r3: each microbatch must see a distinct RNG key, or every
        # scan tick reuses the stage key and dropout masks repeat across
        # microbatches. The pipeline path threads a per-microbatch key in
        # as the LAST capture (split from key_k); the sequential path runs
        # the whole batch once so key_k alone is correct there.
        keyed = micro_caps and key_k is not None

        def fn(params_list, xin, cap_tuple):
            if keyed:
                *cap_vals, mkey = cap_tuple
            else:
                cap_vals, mkey = cap_tuple, key_k
            env = dict(zip(param_names[k], params_list))
            env.update(static)
            env.update(zip(bnames, cap_vals))
            env[names[k]] = xin
            sub = ExecContext(mkey, is_test=ctx.is_test, mesh=ctx.mesh,
                              amp=ctx.amp, data_axis=ctx.data_axis)
            _run_block(blocks[k], env, sub)
            return env[names[k + 1]]
        micro_keys = _jax.random.split(key_k, m) if keyed else None
        return fn, bnames, micro_keys

    mesh = ctx.mesh
    if mesh is None or axis not in mesh.axis_names:
        y = x
        for k in range(n_stages):
            fn, bnames, _ = make_stage(k, micro_caps=False)
            bvals = tuple(c for n, c in zip(cap_names[k], cs[k])
                          if n in bnames)
            y = fn(ps[k], y, bvals)
        return {"Out": [y]}

    data_axis = attrs.get("data_axis")
    if data_axis is not None and data_axis in mesh.axis_names \
            and mesh.shape[data_axis] > 1:
        import warnings
        warnings.warn(
            f"pipeline_hetero: heterogeneous stages run in a FULLY-manual "
            f"shard_map, so the batch is replicated over the "
            f"{data_axis!r}={mesh.shape[data_axis]} mesh axis (no data "
            f"parallelism inside this pipeline). Use isomorphic stages for "
            f"pp×dp composition, or shrink the mesh to the pp axis.",
            stacklevel=2)
    if b % m:
        raise ValueError(f"pipeline_hetero: batch {b} not divisible by "
                         f"num_microbatches {m}")

    def micro(a):
        return a.reshape((m, b // m) + a.shape[1:])

    from ..parallel.pipeline import pipeline_hetero

    stage_fns, caps_tree = [], []
    for k in range(n_stages):
        fn, bnames, micro_keys = make_stage(k, micro_caps=True)
        stage_fns.append(fn)
        stage_caps = tuple(
            micro(c) for n, c in zip(cap_names[k], cs[k]) if n in bnames)
        if micro_keys is not None:
            stage_caps = stage_caps + (micro_keys,)
        caps_tree.append(stage_caps)
    _log_schedule("GPipe-hetero", n_stages, m)
    out = pipeline_hetero(stage_fns, tuple(ps), micro(x), mesh, axis,
                          caps=tuple(caps_tree))
    return {"Out": [jnp.reshape(out, (b,) + tuple(out.shape[2:]))]}
