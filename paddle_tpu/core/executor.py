"""Executor: lowers a Program to one pure JAX function and runs it via XLA.

Reference analog: ``paddle/fluid/framework/executor.cc`` (:172 Run, :349
Prepare, :397 RunPreparedContext — the op-by-op hot loop at :431) plus the
python surface ``python/paddle/fluid/executor.py:295``.

TPU-native redesign: instead of interpreting ops one-by-one on device (which
would strand the MXU between tiny kernel launches), the whole block is traced
into a single pure function ``step(state, feed, rng) -> (fetches, new_state)``
and jit-compiled once per (program version, feed signature) — XLA then owns
fusion, layout, and scheduling. The Scope holds persistable vars (params,
optimizer accumulators) as device arrays; state is donated to the step so
parameter updates alias buffers in HBM instead of copying.

Autodiff: differentiable ops are executed through jax.vjp and recorded on a
tape; the `autodiff` pseudo-op inserted by append_backward walks the tape in
reverse, accumulating cotangents per variable — the functional equivalent of
the reference's GradOpMaker + append_backward (backward.py:558) pass.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import registry
from . import remat as _remat
from .program import (UNIT_ATTR, Block, Program, Variable,
                      default_main_program, grad_var_name, remat_unit_of)
from .scope import Scope, _scope, global_scope

from ..dataio.handle import FetchHandle
from ..faults import fault_point
from ..observability.flight import (get_flight_recorder,
                                    register_dump_section)
from ..observability.http import maybe_serve_from_env
from ..observability.registry import get_registry
from ..observability import scopes as _scopes
from ..observability import setup_account as _setup
from ..observability.steps import get_step_profiler
from ..observability.tracer import step_span, trace_span
from ..observability.watchdog import get_watchdog

import collections
import itertools
import weakref
from types import SimpleNamespace

_RNG_STATE = "@RNG_STATE@"

# Executor telemetry lives in the process-wide registry so one export
# shows executor + serving + user metrics together. Handles are module-
# level: the hot path must not take the registry creation lock per step.
_OBS = get_registry()
_CACHE_HITS = _OBS.counter("executor/cache_hits")
_CACHE_MISSES = _OBS.counter("executor/cache_misses")
_EXECUTE_MS = _OBS.histogram("executor/execute_ms")
_UPDATE_FLUSHES = _OBS.counter("executor/update_flushes")
# counted at lowering, once a barrier: the tape entries whose cotangents
# the backward walk handed on behind one (_walk_tape)
_GRAD_BARRIERS = _OBS.counter("executor/grad_barriers")
_FUSED_GROUPS = _OBS.counter("executor/fused_update_groups")
_FUSED_OPS = _OBS.counter("executor/fused_update_ops")
_INFLIGHT = _OBS.gauge("executor/inflight_steps")
_WATCHDOG = get_watchdog()
_STEPS = get_step_profiler()
_FLIGHT = get_flight_recorder()
# the ordinal every `executor/step` span carries (plain and mesh path alike)
_STEP_ORDINAL = itertools.count()


def _avals_of(args):
    """Shapes and dtypes of a call's arguments: what a step can be lowered
    on again once the call has donated its buffers."""
    return jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(jnp.shape(v), v.dtype), args)


def _record_dispatch(program, sig, fn, dt_ms, compiling, *, feed, avals=None,
                     steps=1, new_state=None):
    """Record one dispatch of the jitted step `fn`: what `Executor.run` and
    `_run_scan` call inside `executor/telemetry`.

    `dt_ms` is the time of the jitted call, which on an accelerator returns
    when the step is enqueued: there `executor/execute_ms` and
    `steps/wall_ms` hold the host's enqueue time, not the step's (on the
    CPU, where dispatch is synchronous, the step's). No rate is computed
    from it; that takes a time that ends at a fetch.

    A compile is observed per signature (a shape-churning feed shows as
    many one-count `executor/compile_ms` histograms) and registers one cost
    entry in the perf ledger: from the AOT executable where `fn` kept one
    (`_AutoLayoutStep`, free), else from a trace-only lower on the avals of
    the call (`fn._avals`; `avals` where `fn` is a bare jit), else from the
    analytic IR walk. `new_state` asks for the state-footprint gauges too;
    `steps` > 1 marks a scan dispatch of that many steps."""
    if compiling:
        _OBS.histogram("executor/compile_ms", sig=sig).observe(dt_ms)
        from ..observability import perf as _perf
        executable = getattr(fn, "_compiled", None)
        if executable is None and _perf.trace_cost_enabled():
            try:
                with _setup.staging("cost"):
                    executable = fn.lower(*(avals or fn._avals))
            except Exception:
                executable = None
        _perf.get_ledger().register(id(program), sig, executable=executable,
                                    program=program, feed=feed, steps=steps)
        if new_state is not None:
            from ..observability.memory import record_state_memory
            record_state_memory(new_state.values())
    else:
        _EXECUTE_MS.observe(dt_ms)
    _STEPS.record(dt_ms, program_id=id(program), sig=sig, compiled=compiling,
                  steps=steps)


# live executors, so the flight recorder can dump which compiled
# signatures were resident when a run died (weak: a GC'd executor's
# cache should not appear in forensics)
_LIVE_EXECUTORS: "weakref.WeakSet" = weakref.WeakSet()


def _fmt_cache_key(key_sig) -> dict:
    try:
        return {"program": f"0x{key_sig[0]:x}", "version": key_sig[1],
                "key": repr(key_sig[2:])[:400]}
    except Exception:
        return {"key": repr(key_sig)[:400]}


def _compiled_signatures_section() -> list:
    out = []
    for exe in list(_LIVE_EXECUTORS):
        out.extend(_fmt_cache_key(k) for k in list(exe._cache))
    return out


register_dump_section("compiled_signatures", _compiled_signatures_section)


# -- persistent compilation cache -------------------------------------------
def _default_compile_cache_dir() -> str:
    """``<checkout>/.jax_cache``, derived from this package's own path: the
    directory is part of jax's cache key, so it must not move between runs
    (no tempfile, pid or clock)."""
    import os
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _enable_compile_cache() -> str:
    """Turn on jax's on-disk compilation cache for this process; runs once,
    when the package is imported, so static-graph, dygraph and bare
    ``jax.jit`` users all compile under it. Where JAX_COMPILATION_CACHE_DIR
    is set jax has already read it and no directory is set here; otherwise
    the cache lives in `_default_compile_cache_dir`. The entry count at
    start lands in the registry so exports tell a cold start (0) from a
    warm one. Returns the directory in effect.

    jax strips locations from the cache's key, and the name scopes the
    lowering writes (observability/scopes.py) live in locations: a step whose
    scopes changed would be handed the executable an earlier build cached,
    with that build's names. Metadata stays out of the key all the same (with
    it in, every edit that moves a source line under a step's trace, and
    every other script that calls the step, compiles cold: PERF.md section 6,
    PR 24); what is hashed instead is the scope scheme itself, through the
    step's name (`scopes.scheme_name`)."""
    import os
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = _default_compile_cache_dir()
        jax.config.update("jax_compilation_cache_dir", d)
    # default thresholds skip small/fast compiles — exactly the programs
    # a restarted trainer recompiles most often; cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    entries = (sum(1 for f in os.listdir(d) if f.endswith("-cache"))
               if os.path.isdir(d) else 0)
    _OBS.gauge("executor/compile_cache_entries_at_start").set(entries)
    return d


_enable_compile_cache()
# the account of every staging in the process (observability/setup_account.py)
_setup.install()


# -- FLAGS_check_nan_inf device-side probe ----------------------------------
_FINITE_PROBE = None


def _check_finite(named_vals) -> None:
    """FLAGS_check_nan_inf parity (operator.cc:949) without the per-step
    host materialization of every state var: ONE jitted all-finite
    reduction runs on device and only its scalar verdict crosses to host;
    names/values are pulled only when it trips."""
    global _FINITE_PROBE
    floats = [(n, v) for n, v in named_vals
              if jnp.issubdtype(getattr(v, "dtype", np.asarray(v).dtype),
                                jnp.floating)]
    if not floats:
        return
    if _FINITE_PROBE is None:
        @jax.jit
        def _probe(vals):
            ok = jnp.bool_(True)
            for v in vals:
                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(v)))
            return ok
        _FINITE_PROBE = _probe
    with _setup.staging("probe", root=False):
        finite = bool(_FINITE_PROBE([v for _, v in floats]))
    if finite:
        return
    for n, v in floats:  # slow path: find and name the offender(s)
        a = np.asarray(v)
        if not np.isfinite(a).all():
            raise FloatingPointError(
                f"NaN/Inf detected in variable {n!r} "
                f"(FLAGS_check_nan_inf is on)")
    raise FloatingPointError(
        "NaN/Inf detected (FLAGS_check_nan_inf is on) but no single "
        "variable reproduced it on host readback")


def _sig_digest(feed_sig) -> str:
    """Short stable label for a feed signature (crc32 of its repr, NOT
    hash() — str hashing is salted per process, and BENCH rounds compare
    these labels across runs), so compile-time histograms can be told
    apart per signature without dumping the whole tuple into a label."""
    import zlib
    return format(zlib.crc32(repr(feed_sig).encode()) & 0xFFFFFFFF, "08x")


def feed_signature(feed_vals) -> tuple:
    """Canonical hashable (name, shape, dtype) signature of a feed dict.

    This is THE compiled-cache key ingredient: Executor.run/run_batched,
    the inference Predictor, and the serving batcher all key their
    executable caches with it, so "same signature" means the same thing
    everywhere (one compile per signature, shared semantics)."""
    return tuple(sorted((str(n), tuple(v.shape), str(v.dtype))
                        for n, v in dict(feed_vals).items()))


def _purge_pending(pend: dict, pid: int) -> None:
    """Drop a dead program's epilogue counters: id() values recycle after
    GC, so a stale (id, i) key would hand a brand-new program an inherited
    steps-since-fold count (worst case the fold fires off-cadence and the
    append log overwrites its tail)."""
    for k in [k for k in pend if k[0] == pid]:
        pend.pop(k, None)


class Place:
    """Device tag. XLA owns placement, so this is descriptive only
    (reference place.h CPUPlace/CUDAPlace variant)."""

    def __init__(self, kind: str, device_id: int = 0):
        self.kind = kind
        self.device_id = device_id

    def __repr__(self):
        return f"{self.kind.upper()}Place({self.device_id})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.device_id) == (other.kind, other.device_id)


def CPUPlace():
    return Place("cpu")


def TPUPlace(device_id: int = 0):
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0):  # API-compat alias; no CUDA in this build
    return Place("tpu", device_id)


class TapeEntry:
    __slots__ = ("in_names", "out_names", "vjp_fn", "out_vals", "nondiff_in")

    def __init__(self, in_names, out_names, vjp_fn, out_vals, nondiff_in):
        self.in_names = in_names
        self.out_names = out_names
        self.vjp_fn = vjp_fn
        self.out_vals = out_vals
        self.nondiff_in = nondiff_in


def _make_key(seed: int):
    """Dropout/init PRNG key. On TPU the default threefry generator burns
    VPU cycles generating mask bits (measured ~100ms/step on the BERT-base
    recipe); XLA's hardware RngBitGenerator ("rbg") is an order of magnitude
    cheaper and statistically fine for dropout."""
    with _setup.staging("probe", root=False):   # the seed's own small jits
        if jax.default_backend() == "tpu":
            # typed key so split()/bernoulli() dispatch on the rbg impl
            return jax.random.key(seed, impl="rbg")
        return jax.random.PRNGKey(seed)


class ExecContext:
    """Per-trace context handed to op implementations."""

    def __init__(self, key, is_test: bool = False, mesh=None, amp=None,
                 remat: bool = False, shard_grad=None, remat_units=None,
                 data_axis=None):
        self._key = key
        self.is_test = is_test
        self.mesh = mesh
        # the mesh axis the batch dim of the feeds is sharded over (None
        # without a mesh): ops GSPMD cannot partition (Mosaic kernels) run
        # per shard of it
        self.data_axis = data_axis
        self.amp = amp  # {'dtype', 'white_list', 'black_list'} or None
        # ShardingStrategy.stage2 hook (CompiledProgram._grad_shard_fn):
        # (target_name, grad) -> grad with a dp sharding constraint, making
        # XLA reduce-scatter the cross-replica gradient sum
        self.shard_grad = shard_grad
        # op-level jax.checkpoint (RematSpec.op_set / legacy
        # BuildStrategy.remat): recompute op internals in the backward
        # instead of saving residuals (trades FLOPs for HBM; the win is on
        # elementwise-heavy ops). True = all ops, or a set of op types.
        self.remat = remat
        # RematSpec (compiler.resolve_remat) — when its unit_policy is set,
        # consecutive ops of one remat block (`program.remat_unit_of`) run as
        # ONE jax.checkpoint region (_run_remat_group)
        self.remat_units = remat_units
        # True while tracing the forward of a remat group: ops run their
        # plain forward (the group's single jax.vjp owns differentiation)
        self.group_forward = False
        # True once the block's `autodiff` op has run: what is lowered after
        # it (everything optimizer.py appends) is the optimizer's, and its
        # name scopes start with `opt/`
        self.after_autodiff = False
        self.tape: List[TapeEntry] = []
        # declared output arity of the op currently being run ({slot: n}) —
        # lets arity-driven kernels (reference: split_ids_op.cc sizes N from
        # its output count) see the OpDesc's declared outputs
        self.out_arity: Dict[str, int] = {}

    def rng(self):
        if self._key is None:
            self._key = _make_key(0)
        self._key, sub = jax.random.split(self._key)
        return sub

    def final_key(self):
        return self._key

    # control-flow ops lower nested blocks through this hook
    def run_block(self, block: Block, env: Dict[str, object]):
        _run_block(block, env, self)


def _zero_cotangent(val):
    if jnp.issubdtype(jnp.asarray(val).dtype, jnp.floating):
        return jnp.zeros_like(val)
    return np.zeros(jnp.shape(val), jax.dtypes.float0)


def _flatten_io(d: Dict[str, List]) -> Tuple[List[str], List]:
    keys = []
    vals = []
    for slot in sorted(d):
        for i, v in enumerate(d[slot]):
            keys.append(f"{slot}:{i}")
            vals.append(v)
    return keys, vals


def _amp_cast(vals_by_slot, op_type, amp):
    """AMP cast insertion at lowering (the reference's cast-op graph pass —
    contrib/mixed_precision/fp16_utils.py — collapsed into trace time)."""
    if amp is None:
        return vals_by_slot
    lo = jnp.bfloat16 if amp["dtype"] == "bfloat16" else jnp.float16

    def cast_to(v, dt):
        a = jnp.asarray(v)
        if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != dt:
            return a.astype(dt)
        return v

    if op_type in amp["white_list"]:
        return {s: [cast_to(v, lo) for v in vs] for s, vs in vals_by_slot.items()}
    if op_type in amp["black_list"]:
        return {s: [cast_to(v, jnp.float32) for v in vs] for s, vs in vals_by_slot.items()}
    return vals_by_slot


_INT64_POLICY_TOLD = False


def _apply_int64_policy(name: str, val, dtype):
    """Explicit x32 narrowing policy (VERDICT r2 weak #6): int64 feeds are
    narrowed to int32 with an OVERFLOW CHECK — values beyond int32 raise
    instead of silently wrapping (a masked bug at 2B+-row embedding scale) —
    plus a single loud policy message instead of a per-step UserWarning.
    Opt into real 64-bit with JAX_ENABLE_X64=1."""
    global _INT64_POLICY_TOLD
    import warnings

    a = np.asarray(val)
    narrow = np.uint32 if a.dtype == np.uint64 else np.int32
    if a.size:
        mx, mn = a.max(), a.min()
        info = np.iinfo(narrow)
        if mx > info.max or mn < info.min:
            raise OverflowError(
                f"feed {name!r}: {a.dtype} values (min {mn}, max {mx}) "
                f"exceed the {np.dtype(narrow).name} range and JAX is in "
                f"x32 mode — set JAX_ENABLE_X64=1 to keep 64-bit integers")
    if not _INT64_POLICY_TOLD:
        _INT64_POLICY_TOLD = True
        warnings.warn(
            "paddle_tpu x32 policy: 64-bit integer feeds are narrowed to "
            "32-bit (range-checked, overflow raises). Set JAX_ENABLE_X64=1 "
            "for true 64-bit. This message is shown once.", stacklevel=3)
    return a.astype(narrow)


def convert_feed_value(block, name: str, val):
    """Convert one feed to a device array with feed-time validation: clear
    errors for unconvertible values and declared-shape mismatches instead
    of raw XLA errors deep in the traced step (reference PrepareData raised
    at feed time too, operator.cc:1031)."""
    var = block._find_var_recursive(name)
    dtype = var.dtype if var is not None else None
    try:
        from .dtypes import dtype_str
        declared64 = (dtype is not None
                      and dtype_str(dtype) in ("int64", "uint64"))
        raw64 = (dtype is None and isinstance(val, np.ndarray)
                 and val.dtype in (np.int64, np.uint64))
        if (declared64 or raw64) and not jax.config.jax_enable_x64:
            if isinstance(val, jax.Array):
                # already a device array — in x32 mode it physically holds
                # 32-bit values, so re-requesting the declared int64 dtype
                # would trip jax's per-call narrowing UserWarning on EVERY
                # step (the bench-tail spam); narrow the REQUEST instead.
                # The once-only policy message covers this path too.
                dtype = (np.uint32 if dtype_str(dtype) == "uint64"
                         else np.int32)
            else:
                val = _apply_int64_policy(name, val, dtype)
                dtype = val.dtype
        arr = jnp.asarray(val, dtype=dtype)
    except (TypeError, ValueError) as e:
        raise type(e)(
            f"feed {name!r}: cannot convert value of type "
            f"{type(val).__name__} to a {dtype or 'device'} array "
            f"({e})") from e
    want = getattr(var, "shape", None)
    if want and len(want) == arr.ndim:
        for axis, (w, got) in enumerate(zip(want, arr.shape)):
            if w not in (-1, None) and w != got:
                raise ValueError(
                    f"feed {name!r}: shape mismatch at dim {axis}: "
                    f"program declares {tuple(want)}, got {arr.shape}")
    elif want and getattr(var, "is_data", False) and len(want) != arr.ndim:
        raise ValueError(
            f"feed {name!r}: rank mismatch: program declares "
            f"{tuple(want)} ({len(want)}-d), got {arr.shape} "
            f"({arr.ndim}-d)")
    return arr


def _op_scope(op, ctx: ExecContext):
    """The name scope every instruction lowered from `op` carries:
    `[opt/][u.<unit>/]op.<op type>` (observability/scopes.py)."""
    return _scopes.op_scope(
        op.type, op.attrs.get(UNIT_ATTR),
        opt=ctx.after_autodiff or op.type in _FUSABLE_UPDATES)


def _run_op(op, env: Dict[str, object], ctx: ExecContext):
    """Lower one op, on the clock of the set-up account: its time less that
    of the ops walked inside it is `setup/trace_op_seconds{op}`."""
    with _setup.walk(op.type):
        _lower_op(op, env, ctx)


def _lower_op(op, env: Dict[str, object], ctx: ExecContext):
    opdef = registry.get_op(op.type)
    ctx.out_arity = {slot: len(names) for slot, names in op.outputs.items()}
    in_vals = {slot: [env[n] for n in names] for slot, names in op.inputs.items()}

    flat_in_names = [n for slot in sorted(op.inputs) for n in op.inputs[slot]]
    diff = opdef.differentiable
    if callable(diff):  # attr-dependent (e.g. `while` with a trip bound)
        diff = diff(op.attrs)
    differentiable = diff and not ctx.is_test and not ctx.group_forward

    custom_grad = None
    if differentiable and flat_in_names and opdef.grad_fn is not None:
        custom_grad = opdef.grad_fn(op.attrs)

    if custom_grad is not None:
        # hand-written gradient (GradOpMaker analog): used where the
        # cotangent is not a dense array — e.g. SelectedRows embedding rows
        with _op_scope(op, ctx):
            ins_c = _amp_cast({s: list(v) for s, v in in_vals.items()},
                              op.type, ctx.amp)
            out = opdef.fn(ctx, ins_c, op.attrs)
        out_names, flat_out_vals = [], []
        for slot in sorted(op.outputs):
            vals = out.get(slot, [])
            names = op.outputs[slot]
            if len(names) != len(vals):
                raise RuntimeError(
                    f"op {op.type}: slot {slot} returned {len(vals)} values, "
                    f"declared {len(names)}")
            for n, v in zip(names, vals):
                env[n] = v
                out_names.append(n)
                flat_out_vals.append(v)

        out_slots = sorted(op.outputs)
        out_counts = [len(op.outputs[s]) for s in out_slots]
        in_slots = sorted(op.inputs)

        def vjp_fn(out_cots, _ins=ins_c, _out=out, _op=op, _ctx=ctx):
            by_slot, i = {}, 0
            for s, c in zip(out_slots, out_counts):
                by_slot[s] = list(out_cots[i:i + c])
                i += c
            # called from the autodiff walk: the scope names the op there
            with _op_scope(_op, _ctx):
                in_cots = custom_grad(_ctx, _ins, _op.attrs, _out, by_slot)
            flat = []
            for s in in_slots:
                got = in_cots.get(s)
                flat.extend(got if got is not None
                            else [None] * len(_op.inputs[s]))
            return tuple(flat)

        nondiff_in = set()
        for slot in opdef.nondiff_inputs:
            nondiff_in.update(op.inputs.get(slot, []))
        ctx.tape.append(TapeEntry(flat_in_names, out_names, vjp_fn,
                                  flat_out_vals, nondiff_in))
        return

    if differentiable and flat_in_names:
        in_slots = sorted(op.inputs)
        in_counts = [len(op.inputs[s]) for s in in_slots]

        def fn(*flat_vals):
            pos = 0
            ins = {}
            for s, c in zip(in_slots, in_counts):
                ins[s] = list(flat_vals[pos:pos + c])
                pos += c
            # AMP casts live INSIDE the differentiated fn so vjp converts
            # cotangent dtypes through the cast automatically. So does the
            # name scope: entered around jax.vjp it would be lost from the
            # backward operations (`transpose(jvp())`), entered here they
            # read `transpose(jvp(u.<unit>/op.<op type>))`
            with _op_scope(op, ctx):
                ins = _amp_cast(ins, op.type, ctx.amp)
                out = opdef.fn(ctx, ins, op.attrs)
            flat_out = []
            for slot in sorted(op.outputs):
                vals = out.get(slot, [])
                if len(vals) != len(op.outputs[slot]):
                    raise RuntimeError(
                        f"op {op.type}: slot {slot} returned {len(vals)} values, "
                        f"declared {len(op.outputs[slot])}")
                flat_out.extend(vals)
            return tuple(flat_out)

        flat_in_vals = [v for s in in_slots for v in in_vals[s]]
        if ctx.remat is True or (isinstance(ctx.remat, (set, frozenset))
                                 and op.type in ctx.remat):
            # selective remat: BuildStrategy.remat may be a set of op types
            # (cheap-to-recompute ops only — BN/activations) instead of
            # all-ops True
            if opdef.own_remat:
                # its gradient rule already keeps its inputs alone: wrapped,
                # its forward rule would run again in the backward pass
                _OBS.counter("remat/op_own", op=op.type).inc()
            else:
                fn = jax.checkpoint(fn)
        flat_out_vals, vjp_fn = jax.vjp(fn, *flat_in_vals)

        out_names = []
        for slot in sorted(op.outputs):
            out_names.extend(op.outputs[slot])
        for n, v in zip(out_names, flat_out_vals):
            env[n] = v

        nondiff_in = set()
        for slot in opdef.nondiff_inputs:
            nondiff_in.update(op.inputs.get(slot, []))
        ctx.tape.append(TapeEntry(flat_in_names, out_names, vjp_fn,
                                  list(flat_out_vals), nondiff_in))
    else:
        with _op_scope(op, ctx):
            out = opdef.fn(ctx, _amp_cast(in_vals, op.type, ctx.amp),
                           op.attrs)
        for slot in sorted(op.outputs):
            vals = out.get(slot, [])
            names = op.outputs[slot]
            if len(names) != len(vals):
                raise RuntimeError(
                    f"op {op.type}: slot {slot} returned {len(vals)} values, "
                    f"declared {len(names)}")
            for n, v in zip(names, vals):
                env[n] = v


def _run_autodiff(op, env, ctx: ExecContext):
    """The `autodiff` pseudo-op: reverse walk of the vjp tape.

    Equivalent of reference append_backward's generated grad-op sequence
    (backward.py:558, accumulation rule _addup_repetitive_outputs_:135),
    executed functionally. The walk runs under the `autodiff` name scope
    (cotangent sums and custom gradients are backward work too); what the
    block lowers after it is the optimizer's.

    One rule decides what XLA may still fuse across that line: a tape entry
    that reads a persistable parameter whose gradient is asked for hands its
    cotangents on behind `optimization_barrier` when the parameter has two
    or more dimensions, so the weight-gradient product runs alone and not
    inside the update that reads it (`lfm2_24b_a2b.train8k` 42,378 ->
    45,930 tokens/s; ledger, PR 44), or when several entries read it, so
    each partial product is done where the walk meets it (PR 30). A mesh
    whose data axis spans devices is excepted from the first: the gradient
    all-reduce already separates product and update there, and the barrier
    cost `ernie_base.dp4_seq512` 2.3% (112,074 -> 109,463; ledger, PR 44).
    `executor/grad_barriers` counts the entries, once a lowering."""
    with _setup.walk(op.type), _scopes.autodiff_scope():
        _walk_tape(op, env, ctx)
    ctx.after_autodiff = True


def _walk_tape(op, env, ctx: ExecContext):
    loss_name = op.attrs["loss_name"]
    targets: Sequence[str] = op.attrs["targets"]
    block = op.block
    target_set = set(targets)

    def _stop_grad(name: str) -> bool:
        # explicitly-requested targets always receive grads (calc_gradient
        # semantics) even if flagged stop_gradient (e.g. data vars)
        if name in target_set:
            return False
        v = block._find_var_recursive(name)
        return bool(v is not None and v.stop_gradient)

    cots: Dict[str, object] = {}
    finished: Dict[str, object] = {}  # target cotangents consumed by the walk
    if "loss_names" in op.attrs:  # calc_gradient: one seed per target
        init_names = op.attrs.get("init_grad_names") or [None] * len(
            op.attrs["loss_names"])
        for ln, ig in zip(op.attrs["loss_names"], init_names):
            if ig is None:
                seed = jnp.ones_like(env[ln])
            else:  # conform seed to the target (e.g. [1] seed for a scalar)
                seed = jnp.asarray(env[ig])
                tgt_shape = jnp.shape(env[ln])
                if seed.shape != tgt_shape:
                    if seed.size == env[ln].size:
                        seed = seed.reshape(tgt_shape)
                    elif seed.size == 1:
                        seed = jnp.broadcast_to(seed.reshape(()), tgt_shape)
                    else:
                        raise ValueError(
                            f"target_gradient for {ln!r} has shape "
                            f"{seed.shape}, target has {tgt_shape}")
            cots[ln] = cots[ln] + seed if ln in cots else seed
    else:
        init_name = op.attrs.get("init_grad_name")
        if init_name is not None:
            cots[loss_name] = env[init_name]
        else:
            cots[loss_name] = jnp.ones_like(env[loss_name])

    # Which entries hand their cotangents on behind a barrier. Left alone
    # XLA puts a weight-gradient product off until the optimizer wants it
    # and fuses it into the update, where it runs far under its own pace
    # (LFM2 `matmul_ms` 181.3 -> 143.3 of a 386.6 ms step once it ran
    # alone; ledger, PR 44), or, for a parameter that several entries read
    # (a layer applied more than once: models/ouro.py), holds every partial
    # product's operands until then. So an entry that reads a persistable
    # parameter whose gradient is wanted ends its products where the walk
    # meets it, if the parameter is a matrix or has more than one reader;
    # vectors' reductions fuse into their neighbours, which is wanted.
    # Under a mesh that sums gradients across devices the all-reduce
    # already stands between a product and its update, and a barrier there
    # only cost (dp4 `tokens_per_s` 112,074 -> 109,463, `step_hbm` 14.35
    # -> 14.97; ledger, PR 44): there only the second reason counts.
    reads = collections.Counter(
        n for entry in ctx.tape for n in set(entry.in_names))
    mesh_sums = (ctx.mesh is not None and ctx.data_axis is not None
                 and ctx.mesh.shape[ctx.data_axis] > 1)

    def _wants_barrier(name: str) -> bool:
        var = block._find_var_recursive(name)
        if not getattr(var, "persistable", False) or _stop_grad(name):
            return False
        return reads[name] > 1 or (
            not mesh_sums and name in target_set and len(var.shape) >= 2)

    behind_barrier = {n for n in reads if _wants_barrier(n)}

    for entry in reversed(ctx.tape):
        if not any(n in cots for n in entry.out_names):
            continue
        out_cots = tuple(
            cots.get(n, _zero_cotangent(v))
            for n, v in zip(entry.out_names, entry.out_vals))
        in_cots = entry.vjp_fn(out_cots)
        # non-SSA names: this entry's outputs are now consumed — clear them
        # so an op whose inputs reuse an output name (while/assign carries)
        # replaces the cotangent instead of double-counting it. Requested
        # targets keep their first-consumed (= final-instance) cotangent.
        for n in entry.out_names:
            g = cots.pop(n, None)
            if g is not None and n in target_set and n not in finished:
                finished[n] = g
        for name, g in zip(entry.in_names, in_cots):
            if g is None or name in entry.nondiff_in or _stop_grad(name):
                continue
            if isinstance(g, np.ndarray) and g.dtype == jax.dtypes.float0:
                continue
            if name in cots:
                cots[name] = cots[name] + g
            else:
                cots[name] = g
        if behind_barrier.intersection(entry.in_names):
            held = [n for n in dict.fromkeys(entry.in_names)
                    if isinstance(cots.get(n), jax.Array)]
            if held:
                _GRAD_BARRIERS.inc()
                cots.update(zip(held, jax.lax.optimization_barrier(
                    tuple(cots[n] for n in held))))

    for t in targets:
        gname = grad_var_name(t)
        if t in finished:
            g = finished[t]
        else:
            g = cots.get(t, jnp.zeros_like(env[t]))
        if ctx.shard_grad is not None:
            g = ctx.shard_grad(t, g)
        env[gname] = g


# Horizontally-fusable parameter-update ops: N independent per-parameter
# updates collapse into ONE update on concatenated flats. XLA does not
# horizontally fuse independent elementwise subgraphs, so a 161-parameter
# ResNet-50 momentum step otherwise lowers to 157 tiny kernels costing
# ~11 ms/step of launch latency (xplane-measured) vs ~1 ms fused.
# Reference analog: coalesce_tensor_op.cc + the fused_all_reduce group-fusion
# idea applied to the optimizer.
_FUSABLE_UPDATES = {
    "sgd": {
        "flat_in": ("Param", "Grad"), "flat_out": ("ParamOut",),
        "scalar_in": ("LearningRate",), "scalar_out": ()},
    "momentum": {
        "flat_in": ("Param", "Grad", "Velocity"),
        "flat_out": ("ParamOut", "VelocityOut"),
        "scalar_in": ("LearningRate",), "scalar_out": ()},
    # adam/adamw are deliberately NOT fusable: their Beta*Pow accumulators
    # are per-parameter state — flattening a group onto ops[0]'s pows would
    # corrupt any accumulator not in lockstep (e.g. a param added by a
    # later minimize() call).
}


def _attrs_sig(attrs):
    """Fusion-group attr signature. Any non-scalar attr (list/array) makes
    the op not-fusable (None): silently dropping it from the key would let
    two ops differing only in that attr fuse and run with ops[0]'s attrs."""
    try:
        sig = []
        for k, v in attrs.items():
            if not isinstance(v, (int, float, bool, str)):
                return None
            sig.append((k, v))
        return tuple(sorted(sig))
    except Exception:
        return None


def _group_key(op, env, mode):
    """Fusion-compatibility key; None = not fusable (e.g. sparse grads, or
    a large parameter in "auto" mode)."""
    spec = _FUSABLE_UPDATES[op.type]
    sig = _attrs_sig(op.attrs)
    if sig is None:
        return None
    dts = []
    for slot in spec["flat_in"]:
        if slot not in op.inputs or len(op.inputs[slot]) != 1:
            return None
        v = env.get(op.inputs[slot][0])
        if not hasattr(v, "dtype") or not hasattr(v, "ravel"):
            return None  # SelectedRows / host values take the per-op path
        dts.append(str(v.dtype))
    if mode == "auto":
        p = env.get(op.inputs["Param"][0])
        if int(np.prod(jnp.shape(p)) or 1) > _FUSE_SMALL_MAX_ELEMS:
            return None
    lr = tuple(op.inputs.get("LearningRate", ()))
    return (op.type, sig, lr, tuple(dts))


def _run_update_group(ops, env, ctx: ExecContext):
    with _setup.walk(ops[0].type):
        _lower_update_group(ops, env, ctx)


def _lower_update_group(ops, env, ctx: ExecContext):
    opdef = registry.get_op(ops[0].type)
    spec = _FUSABLE_UPDATES[ops[0].type]
    shapes = [jnp.shape(env[op.inputs["Param"][0]]) for op in ops]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offsets = list(np.cumsum(sizes)[:-1])
    with _op_scope(ops[0], ctx):    # the flats and the splits are its work
        ins = {}
        for slot in spec["flat_in"]:
            ins[slot] = [jnp.concatenate(
                [jnp.ravel(env[op.inputs[slot][0]]) for op in ops])]
        for slot in spec["scalar_in"]:
            if slot in ops[0].inputs:
                ins[slot] = [env[ops[0].inputs[slot][0]]]
        out = opdef.fn(ctx, ins, ops[0].attrs)
        for slot in spec["flat_out"]:
            parts = jnp.split(out[slot][0], offsets)
            for op, part, shp in zip(ops, parts, shapes):
                env[op.outputs[slot][0]] = part.reshape(shp)
    for slot in spec["scalar_out"]:
        if slot in ops[0].outputs and slot in out:
            for op in ops:
                env[op.outputs[slot][0]] = out[slot][0]


# "auto" fuses only parameters this small into a flat update. Every mode
# was MEASURED SLOWER than per-op updates on v5e and stays off by default:
# "all" pays a tiled-layout relayout round-trip on conv/matmul weights
# (ResNet-50 52→97 ms, BERT 318→343 ms); even "auto" regresses ~2 ms
# because XLA already fuses the small per-BN-vector updates into the
# adjacent BN statistics fusions, which grouping breaks. Kept for runtimes
# where kernel-launch latency dominates per-byte copy cost.
_FUSE_SMALL_MAX_ELEMS = 65536


def _fuse_updates_mode() -> str:
    import os
    v = os.environ.get("PDTPU_FUSE_UPDATES", "0")
    return {"0": "off", "1": "all"}.get(v, v)


def _remat_group_eligible(op) -> bool:
    """Can `op` join a remat-unit group? Groups differentiate through ONE
    jax.vjp over the whole unit, so members must be plainly differentiable:
    custom-grad ops (sparse cotangents), non-differentiable ops (grads must
    stay cut), control flow (nested blocks) and the update/autodiff ops all
    keep their per-op path."""
    if op.type == "autodiff" or op.type in _FUSABLE_UPDATES:
        return False
    try:
        opdef = registry.get_op(op.type)
    except Exception:
        return False
    if opdef.grad_fn is not None:
        return False
    diff = opdef.differentiable
    if callable(diff):
        try:
            diff = diff(op.attrs)
        except Exception:
            return False
    if not diff:
        return False
    for v in op.attrs.values():
        if isinstance(v, Block):
            return False
    return True


def _plan_remat_items(block: Block, ctx: ExecContext):
    """Partition block.ops into ("op", None, op) singles and
    ("group", decision, [ops]) maximal runs of consecutive ops sharing a
    remat block (`program.remat_unit_of`) whose unit decision (RematSpec.unit_policy) is
    truthy. Cheap when no policy is active (the common path)."""
    spec = ctx.remat_units
    pred = getattr(spec, "unit_policy", None) if spec is not None else None
    if pred is None or ctx.is_test:
        return [("op", None, op) for op in block.ops]
    items = []
    decisions: Dict[str, object] = {}
    cur_unit, cur_dec, cur_ops = None, None, []

    def flush():
        nonlocal cur_unit, cur_dec, cur_ops
        if cur_ops:
            items.append(("group", cur_dec, cur_ops))
        cur_unit, cur_dec, cur_ops = None, None, []

    for op in block.ops:
        unit = remat_unit_of(op)
        dec = None
        if unit is not None and _remat_group_eligible(op):
            if unit not in decisions:
                try:
                    # the policy is asked by the name `remat_unit` was given
                    decisions[unit] = pred(unit.rsplit("/", 1)[-1])
                except Exception:
                    decisions[unit] = False
            dec = decisions[unit]
            if not dec or dec == "none":
                dec = None
        if dec is None:
            flush()
            items.append(("op", None, op))
        elif unit == cur_unit:
            cur_ops.append(op)
        else:
            flush()
            cur_unit, cur_dec, cur_ops = unit, dec, [op]
    flush()
    return items


def _run_remat_group(ops, decision, env: Dict[str, object],
                     ctx: ExecContext):
    """`_lower_remat_group` on the set-up account's clock: the unit's ops
    hold their own time, `setup/trace_op_seconds{op="remat_group"}` what the
    wrapping (`jax.checkpoint`, `jax.vjp`) costs."""
    with _setup.walk("remat_group"):
        _lower_remat_group(ops, decision, env, ctx)


def _lower_remat_group(ops, decision, env: Dict[str, object],
                       ctx: ExecContext):
    """Run a remat unit as ONE checkpointed function: forward now, and a
    single tape entry whose vjp recomputes the whole unit from its entry
    values under the policy's `policy=` (dots_saveable etc.). This is the
    per-model-block form of remat — per-op jax.checkpoint still saves every
    op-boundary activation; wrapping the unit drops those too. What the unit
    keeps all the same (`RematSpec.names_for`: op outputs, named here, and
    residuals that ops name themselves) is weighed while it is traced, and
    the gauges `remat/kept_values{unit}` / `remat/kept_bytes{unit}` say it."""
    spec = ctx.remat_units
    unit = remat_unit_of(ops[0])
    keep = frozenset(spec.names_for(unit))
    reads, read_set, writes, write_set = [], set(), [], set()
    for op in ops:
        for slot in sorted(op.inputs):
            for n in op.inputs[slot]:
                if n not in write_set and n not in read_set:
                    read_set.add(n)
                    reads.append(n)
        for slot in sorted(op.outputs):
            for n in op.outputs[slot]:
                if n not in write_set:
                    write_set.add(n)
                    writes.append(n)
    in_names = reads
    # what the rest of the block reads of the unit is what the backward pass
    # has cotangents for; every other value the unit writes goes out beside
    # them (a fetch may name it) and takes none, so the walk makes no zeros
    # for it and the backward's barrier holds none
    read_outside = _read_outside(ops)
    out_names = [n for n in writes if n in read_outside]
    aux_names = [n for n in writes if n not in read_outside]
    # one split per group, closed over (not a traced argument): the
    # checkpointed backward replays the SAME key, so recomputed dropout
    # masks match the forward exactly
    gkey = ctx.rng()

    def fwd(*vals):
        sub = ExecContext(gkey, is_test=ctx.is_test, mesh=ctx.mesh,
                          amp=ctx.amp, remat=False,
                          shard_grad=ctx.shard_grad,
                          data_axis=ctx.data_axis)
        sub.group_forward = True
        local = dict(zip(in_names, vals))
        for op in ops:
            _run_op(op, local, sub)
            for n in op.output_names():
                if n in keep:
                    local[n] = _remat.kept(local[n], n)
        return (tuple(local[n] for n in out_names),
                tuple(local[n] for n in aux_names))

    wrapped = jax.checkpoint(fwd, policy=spec.jax_policy(decision, unit))
    with _remat.weighing(keep) as weighed:
        out_vals, vjp_fn, aux_vals = jax.vjp(
            wrapped, *[env[n] for n in in_names], has_aux=True)
    env.update(zip(out_names, out_vals))
    env.update(zip(aux_names, aux_vals))
    reg = get_registry()
    reg.gauge("remat/kept_values", unit=unit).set(weighed.values)
    reg.gauge("remat/kept_bytes", unit=unit).set(weighed.bytes)
    # an input is non-differentiable for the GROUP only if every use of it
    # inside is through a nondiff slot
    used_diff, used_nondiff = set(), set()
    for op in ops:
        nd_slots = registry.get_op(op.type).nondiff_inputs
        for slot, names in op.inputs.items():
            (used_nondiff if slot in nd_slots else used_diff).update(names)
    nondiff_in = (used_nondiff - used_diff) & set(in_names)
    ctx.tape.append(TapeEntry(list(in_names), list(out_names), vjp_fn,
                              list(out_vals), nondiff_in))


def _read_outside(ops) -> set:
    """The names that ops of the block other than `ops` read (the ops of
    their nested blocks too), or that its `autodiff` ops name as a loss or a
    target."""
    inside = {id(op) for op in ops}
    names = set()

    def visit(block_ops):
        for op in block_ops:
            if id(op) in inside:
                continue
            for slot_names in op.inputs.values():
                names.update(slot_names)
            for v in op.attrs.values():
                if isinstance(v, Block):
                    visit(v.ops)
            if op.type == "autodiff":
                for key in ("loss_name", "init_grad_name"):
                    if op.attrs.get(key):
                        names.add(op.attrs[key])
                for key in ("loss_names", "targets", "init_grad_names"):
                    names.update(n for n in op.attrs.get(key) or () if n)

    visit(ops[0].block.ops)
    return names


def eval_inference_block(program, env: Dict[str, object]) -> Dict[str, object]:
    """Run `program`'s global block EAGERLY over `env` (merged state +
    feeds), mutating and returning it — every intermediate var stays
    visible in `env` afterwards. No jit, no signature cache: this is the
    observation path (int8 calibration reads activation ranges out of
    it, debuggers read anything) — per-request serving goes through the
    Predictor's compiled route instead."""
    _run_block(program.global_block(), env, ExecContext(None, is_test=True))
    return env


def _run_block(block: Block, env: Dict[str, object], ctx: ExecContext):
    mode = _fuse_updates_mode()
    items = _plan_remat_items(block, ctx)
    if mode == "off":
        for kind, dec, entry in items:
            if kind == "group":
                _run_remat_group(entry, dec, env, ctx)
            elif entry.type == "autodiff":
                _run_autodiff(entry, env, ctx)
            else:
                _run_op(entry, env, ctx)
        return
    pending: List = []          # fusable update ops awaiting flush
    pending_in: set = set()
    pending_out: set = set()

    def flush():
        if not pending:
            return
        # counted at TRACE time (once per compiled signature, not per
        # step): how many flush points the lowering hit and how many
        # update ops actually fused — the observable for tuning
        # PDTPU_FUSE_UPDATES
        _UPDATE_FLUSHES.inc()
        groups: Dict[object, List] = {}
        singles: List = []
        for p in pending:
            key = _group_key(p, env, mode)
            if key is None:
                singles.append(p)
            else:
                groups.setdefault(key, []).append(p)
        for ops_ in groups.values():
            if len(ops_) == 1:
                singles.append(ops_[0])
            else:
                _FUSED_GROUPS.inc()
                _FUSED_OPS.inc(len(ops_))
                _run_update_group(ops_, env, ctx)
        for p in singles:
            _run_op(p, env, ctx)
        pending.clear()
        pending_in.clear()
        pending_out.clear()

    for kind, dec, entry in items:
        if kind == "group":
            # remat units are model-forward regions; any pending updates
            # must complete first (conservative, and trivially correct)
            flush()
            _run_remat_group(entry, dec, env, ctx)
            continue
        op = entry
        if op.type in _FUSABLE_UPDATES:
            names_in = {n for ns in op.inputs.values() for n in ns}
            names_out = {n for ns in op.outputs.values() for n in ns}
            # a fusable op that depends on (or clobbers) a pending op's
            # output must not join its group — flush so updates on the same
            # parameter stay ordered
            if names_in & pending_out or names_out & (pending_in
                                                      | pending_out):
                flush()
            pending.append(op)
            pending_in.update(names_in)
            pending_out.update(names_out)
            continue
        names_in = {n for ns in op.inputs.values() for n in ns}
        names_out = {n for ns in op.outputs.values() for n in ns}
        if (op.type == "autodiff" or names_in & pending_out
                or names_out & (pending_in | pending_out)):
            flush()
        if op.type == "autodiff":
            _run_autodiff(op, env, ctx)
        else:
            _run_op(op, env, ctx)
    flush()


def _make_step(program, fetch_names, out_state_names, remat_spec, mesh=None,
               data_axis=None, shard_grad=None, pads=None, fsdp_sh=None):
    """The pure (state, feed, key) -> (fetches, new_state, key) step of
    `program`: what `Executor._build` and `CompiledProgram._build` jit and
    what `_run_scan` carries through its scan. `remat_spec` is the resolved
    RematSpec of whoever builds the step; the rest is the mesh's and stays
    empty without one (`CompiledProgram._make_step` fills it in): `pads`
    {name: (logical_dim0, padded_dim0)} and `fsdp_sh` {name: sharding}."""
    block = program.global_block()
    amp = getattr(program, "_amp", None)
    pads = pads or {}
    fsdp_sh = fsdp_sh or {}

    def step(state, feed, key):
        env = dict(state)
        # padded-boundary leaves: drop the pad rows before any op sees
        # the value (ops run on the logical shape; GSPMD keeps the
        # slice sharded — uneven tiles are legal INSIDE the program)
        for n, (d, _dpad) in pads.items():
            if n in env and env[n].shape[0] != d:
                env[n] = jax.lax.slice_in_dim(env[n], 0, d, axis=0)
        for n, sh in fsdp_sh.items():
            if n in env:
                env[n] = jax.lax.with_sharding_constraint(env[n], sh)
        env.update(feed)
        ctx = ExecContext(key, mesh=mesh, amp=amp, remat=remat_spec.op_set,
                          remat_units=remat_spec, shard_grad=shard_grad,
                          data_axis=data_axis)
        _run_block(block, env, ctx)
        fetches = [env[n] for n in fetch_names]
        new_state = {}
        for n in out_state_names:
            if n not in env:
                continue
            v = env[n]
            pad = pads.get(n)
            if pad is not None and v.shape[0] == pad[0]:
                v = jnp.pad(v, [(0, pad[1] - pad[0])]
                            + [(0, 0)] * (v.ndim - 1))
            new_state[n] = v
        return fetches, new_state, ctx.final_key()

    # the compile cache hashes the step's name and not its metadata: the
    # name carries the names the lowering will write
    step.__name__ = _scopes.scheme_name("step", program)
    return step


class _Step:
    """What `Executor.compiled_step` and `scopes.hottest_step` need of a
    step: how often it was dispatched, and its executable. A jitted function
    keeps no executable to hand out, so the step remembers the avals of its
    first call and lowers on them again (the same trace, so a hit of the
    compile cache). The mesh path's step is this class as it is."""

    def __init__(self, jitted):
        self._jitted = jitted
        self.calls = 0
        self._avals = None
        self._lowered_again = None
        _scopes.track_step(self)

    def _first(self, state, feed, key):
        """The first dispatch, on the set-up account: what jax stages inside
        it is the call's, the rest of it the phase `first_run`. The steady
        body runs once inside, as it is."""
        self._avals = _avals_of((state, feed, key))
        with _setup.staging("call"):
            self._stage(state, feed, key)
            with _setup.phase("first_run", self.name):
                return self(state, feed, key)

    def _stage(self, state, feed, key):
        """What is compiled ahead of the first call: on a plain jit,
        nothing."""

    @property
    def name(self) -> str:
        """The jitted function's name: the `step` of the account's records."""
        return getattr(self._jitted, "__name__", "?")

    def __call__(self, state, feed, key):
        if self._avals is None:
            return self._first(state, feed, key)
        self.calls += 1
        return self._jitted(state, feed, key)

    def __getattr__(self, name):
        # what else callers ask of a jitted function (`lower`, `trace`)
        if name == "_jitted":
            raise AttributeError(name)
        return getattr(self._jitted, name)

    def compiled(self):
        if self._lowered_again is None:
            if self._avals is None:
                raise RuntimeError("the step has not been dispatched yet")
            with _setup.staging("executable"):
                self._lowered_again = self._jitted.lower(
                    *self._avals).compile()
        return self._lowered_again


class _AutoLayoutStep(_Step):
    """jit wrapper that lets XLA choose (and keep) the parameter layouts.

    With default row-major entry layouts, every conv/matmul weight is
    relayouted on entry AND exit of each step — the xplane trace showed ~12 ms
    of a 54 ms ResNet-50 step going to 150+ tiny copy/relayout+update kernels,
    and the layout mismatch also defeats buffer donation (the "donated
    buffers were not usable" warnings). Compiling with Layout.AUTO on the
    state argument and the new-state output keeps parameters in XLA's
    preferred layout across steps: the one-time device_put at first call pays
    the relayout once, after which outputs flow back in as inputs unchanged
    and donation aliases in place.
    """

    def __init__(self, step):
        self._step = step
        self._plain = jax.jit(step, donate_argnums=(0,))
        super().__init__(self._plain)
        # previous step's output state (name -> array), retained so the
        # steady-state path can verify leaves BY IDENTITY — `.format`
        # builds a Format object per access, ~0.5 µs/leaf, which at
        # ResNet-50's 430 state leaves was 4 ms/step of dispatch time.
        # Holding the refs also makes `x is last[n]` immune to id reuse.
        self._last_out = {}
        self._auto = None
        self._compiled = None
        self._in_format = None
        self._in_shapes = None  # name -> shape the AOT step was traced for
        self._sig = None  # (state, feed) aval signature the AOT step expects
        try:
            from jax.experimental.layout import Format, Layout
            auto = Format(layout=Layout.AUTO)
            self._auto = jax.jit(step, donate_argnums=(0,),
                                 in_shardings=(auto, None, None),
                                 out_shardings=(None, auto, None))
        except Exception:  # pragma: no cover - layout API unavailable
            pass

    @staticmethod
    def _signature(state, feed):
        def _dt(v):
            dt = getattr(v, "dtype", None)
            return str(dt) if dt is not None else str(np.asarray(v).dtype)
        return tuple(sorted(
            (n, tuple(jnp.shape(v)), _dt(v))
            for d in (state, feed) for n, v in d.items()))

    @staticmethod
    def _accumulator_bases(state):
        """Map optimizer-state var name -> its base parameter name.
        Accumulators are named '{param}_{Optimizer}_{acc}' (optimizer.py
        _add_accumulator) and share the param's shape+dtype; layout matching
        below keys off this."""
        bases = {}
        names = sorted(state, key=len, reverse=True)
        for n in state:
            for p in names:
                if (p != n and len(p) < len(n) and n.startswith(p)
                        and n[len(p)] in "._"
                        and jnp.shape(state[p]) == jnp.shape(state[n])
                        and getattr(state[p], "dtype", None)
                        == getattr(state[n], "dtype", None)):
                    bases[n] = p
                    break
        return bases

    def _relayout_accumulators(self, state, feed, key):
        """Second compile pass: pin every optimizer accumulator to its
        base parameter's AUTO-chosen layout, guarding against the AUTO
        solver choosing DIFFERENT tilings for a param and its velocity
        (which would fuse a physical tile-format transpose into every
        update). On the ResNet-50 recipe the solver already agrees
        (trace-audited: zero mismatches in the train-step module — the
        apparent 'slow update kernels' were wgrad reductions reading
        activations, already near stream rate), so this pass usually
        compiles nothing; it exists so a future solver change can't
        silently regress update bandwidth."""
        from jax.experimental.layout import Format

        in_state = dict(self._compiled.input_formats[0][0])
        out_fmts = self._compiled.output_formats
        bases = self._accumulator_bases(state)
        changed = False
        for n, p in bases.items():
            if (in_state[n].layout != in_state[p].layout):
                in_state[n] = Format(layout=in_state[p].layout)
                changed = True
        if not changed:
            return
        # outputs: new_state leaves mirror the (possibly overridden) input
        # formats so step-over-step state flows back in without relayout
        out_state = {n: in_state.get(n, f)
                     for n, f in out_fmts[1].items()}
        relayout = jax.jit(
            self._step, donate_argnums=(0,),
            in_shardings=(in_state, None, None),
            out_shardings=(out_fmts[0], out_state, out_fmts[2]))
        with _setup.staging("relayout"):
            self._compiled = relayout.lower(state, feed, key).compile()
        self._in_format = self._compiled.input_formats[0][0]

    def compiled(self):
        """The AUTO-layout executable where there is one, else the plain
        jit's."""
        if self._compiled is not None:
            return self._compiled
        return super().compiled()

    def _stage(self, state, feed, key):
        """The AUTO-layout compile of the first dispatch and, as the phase
        `relayout`, the look at the accumulators' layouts after it (the
        `device_put`s of the steady body's slow path fall in `first_run`)."""
        if self._auto is not None and self._compiled is None:
            # huge state leaves (Criteo-scale embedding tables): a layout
            # disagreement between the AUTO solver and the producing
            # program would force a relayout COPY of the leaf — for a
            # >2GB table that transient doubles its footprint and OOMs
            # the chip. Default layouts are deterministic per shape/dtype
            # across programs, so the plain jit threads such state with
            # no copy; the AUTO pass matters for many-leaf convnet state,
            # not single-giant-table programs.
            if any(getattr(v, "nbytes", 0) > (2 << 30)
                   for v in state.values()):
                self._auto = None
        if self._auto is not None and self._compiled is None:
            try:
                self._compiled = self._auto.lower(state, feed, key).compile()
                self._in_format = self._compiled.input_formats[0][0]
                self._in_shapes = {n: jnp.shape(v) for n, v in state.items()}
                self._sig = self._signature(state, feed)
                try:
                    with _setup.phase("relayout", self.name):
                        self._relayout_accumulators(state, feed, key)
                except Exception as e:  # keep the AUTO-layout executable
                    _setup.auto_layout_fallback(
                        e, self.name, "the AUTO-layout step without the "
                        "accumulators' pass")
            except Exception as e:  # backend without AUTO layout support
                _setup.auto_layout_fallback(e, self.name, "the plain jit")
                self._auto = None
                self._compiled = None
                self._in_format = None
                self._in_shapes = None

    def __call__(self, state, feed, key):
        if self._avals is None:
            return self._first(state, feed, key)
        self.calls += 1
        if self._compiled is not None:
            # steady-state fast path: after step 1 every state leaf is the
            # previous step's output, already in the compiled entry format —
            # skip the O(vars) signature hash + asarray per leaf (profiled
            # at ~13 ms/step host time on the ResNet-50 recipe, it kept the
            # dispatch from hiding under device compute). Identity check
            # first: a leaf we produced (or already format-verified) needs
            # no Format reconstruction.
            fmts = self._in_format
            shapes = self._in_shapes
            last = self._last_out
            # jax Format does NOT encode shape, so the non-identity branch
            # must also check the compiled aval's shape — a var swapped via
            # scope.set_var to a same-rank different shape (e.g. a grown
            # embedding table) must fall through to the signature path and
            # the retraceable plain jit, not crash the AOT executable
            if all(v is last.get(n)
                   or (getattr(v, "format", None) == fmts[n]
                       and jnp.shape(v) == shapes[n])
                   for n, v in state.items()):
                out = self._compiled(state, feed, key)
                # a step that takes no state (a startup program) has no leaf
                # to know again, and holding what it made would keep alive
                # every array the scope replaces afterwards (seeded weights
                # set over the startup's draw: a parameter set's bytes)
                if state:
                    self._last_out = out[1]
                return out
            # slow path (first call, or a var swapped via scope.set_var):
            # validate shapes/dtypes — checkpoint surgery may have replaced
            # a var with a different shape; the AOT executable can't
            # retrace, but the plain jit can
            if self._sig != self._signature(state, feed):
                return self._plain(state, feed, key)
            # per-leaf: device_put only arrays not already in the compiled
            # entry format (device_put of an already-in-format tiled array
            # is NOT a no-op on all backends — it can launch a relayout
            # program the runtime rejects for exotic tilings)
            # (on the first call a part of the set-up account's `first_run`)
            state = {
                n: (v if getattr(v, "format", None) == fmts[n]
                    else jax.device_put(v, fmts[n]))
                for n, v in state.items()
            }
            return self._compiled(state, feed, key)
        return self._plain(state, feed, key)


class Executor:
    """python/paddle/fluid/executor.py:295 parity, XLA-compiled.

    exe = Executor(TPUPlace()); exe.run(startup); exe.run(main, feed, fetch_list)
    """

    def __init__(self, place: Optional[Place] = None):
        self.place = place or TPUPlace()
        self._cache = {}
        self._state_names_cache = None
        # DeviceLoaders this executor spun up (train_from_dataset); weak so
        # a finished loop's loader can die without waiting for close()
        self._loaders: "weakref.WeakSet" = weakref.WeakSet()
        _LIVE_EXECUTORS.add(self)
        # live introspection plane: PDTPU_INTROSPECT_PORT alone makes
        # any training process scrapeable (/metrics, /healthz, /debug)
        maybe_serve_from_env()

    # -- lowering ----------------------------------------------------------
    def _state_names(self, program: Program, scope: Scope) -> List[str]:
        # cached single entry, rebuilt when the program version or any
        # scope in the lookup chain mutates its KEY SET: rebuilding the
        # list walks every program var and cost ~0.8 ms/step on
        # ResNet-50.  The cache holds STRONG refs to program+scope (so
        # identity comparison can't alias a recycled id) and the
        # per-chain-scope key-set generations (has_var walks parents, so
        # a var added to a PARENT scope must also invalidate; a
        # generation counter, unlike len(_vars), catches erase-one +
        # add-another).
        chain_sizes = []
        s = scope
        while s is not None:
            chain_sizes.append(s._keyset_gen)
            s = s.parent
        cached = self._state_names_cache
        if (cached is not None and cached[0] is program
                and cached[1] == program._version and cached[2] is scope
                and cached[3] == chain_sizes):
            return cached[4]
        names = sorted({v.name for v in program.list_vars()
                        if v.persistable and scope.has_var(v.name)})
        self._state_names_cache = (program, program._version, scope,
                                   chain_sizes, names)
        return names

    # the plain path's answers to what `run` asks of whoever owns the step;
    # a CompiledProgram gives the mesh's under the same names
    def _convert_feeds(self, block, feed):
        return {name: convert_feed_value(block, name, val)
                for name, val in feed.items()}

    def _state_in(self, program, scope, state_names):
        """The state leaves as device arrays, and the RNG key."""
        state = {n: scope.find_var(n) for n in state_names}
        key = scope.find_var(_RNG_STATE)
        if key is None:
            key = _make_key(program.random_seed or 0)
        # a scope that last ran through a ZeRO-padded CompiledProgram
        # boundary holds some leaves padded past their declared shape —
        # slice the pad off before tracing the unsharded step
        zero_pads = getattr(program, "_zero_padded", None)
        if zero_pads:
            for n, shp in zero_pads.items():
                v = state.get(n)
                if (v is not None and shp and getattr(v, "shape", None)
                        and tuple(v.shape) != tuple(shp)
                        and v.shape[0] > shp[0]):
                    state[n] = jnp.asarray(v)[:shp[0]]
        state = {n: (v if isinstance(v, jax.Array) else jnp.asarray(v))
                 for n, v in state.items()}
        return state, key

    def _make_step(self, program, fetch_names, out_state_names):
        # without a CompiledProgram's strategies, what the program's builder
        # asked for its own remat units (`program.remat_policy`)
        from .compiler import resolve_remat
        return _make_step(program, fetch_names, out_state_names,
                          resolve_remat(program=program))

    def _build(self, program: Program, feed_names, fetch_names, state_names,
               out_state_names):
        return _AutoLayoutStep(
            self._make_step(program, fetch_names, out_state_names))

    def compiled_step(self, program=None):
        """The compiled executable (`jax.stages.Compiled`) behind
        `run(program, ...)`: of the signatures this executor has run the
        program with, the one dispatched most often. For its
        `memory_analysis()`, `cost_analysis()` and `as_text()`;
        `observability.scopes.op_scopes` reads the last."""
        from .compiler import CompiledProgram

        if isinstance(program, CompiledProgram):
            steps = list(program._cache.values())
        else:
            program = program or default_main_program()
            steps = [fn for key, fn in self._cache.items()
                     if key[0] == id(program) and hasattr(fn, "compiled")]
        steps = [fn for fn in steps if fn.calls]
        if not steps:
            raise RuntimeError("this executor has not run the program yet")
        return max(steps, key=lambda fn: fn.calls).compiled()

    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, np.ndarray]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        return_handle: bool = False,
    ):
        """Run `program`: feed → execute → fetch (reference executor.py:539).

        return_handle=True: skip the fetch materialization entirely and
        return a :class:`FetchHandle` over the still-computing jax arrays
        — jax's async dispatch keeps the device busy while the host
        prepares the next step; `.numpy()` on the handle is the sync
        point. Results are bitwise-identical to return_numpy=True."""
        from .compiler import CompiledProgram

        # one root span per call, carrying the step's ordinal; its children
        # are the phases of the call: feed, state_in, run, telemetry,
        # state_out, epilogue, fetch. One sequence for a Program and a
        # CompiledProgram: `owner` answers for what a mesh changes (feed and
        # state placement, the key, the jit and its cache), and the call's
        # span and sites keep the name of the path
        with step_span("executor/step", next(_STEP_ORDINAL)):
            with trace_span("executor/feed"):
                compiled = (program if isinstance(program, CompiledProgram)
                            else None)
                if compiled is None:
                    owner, site, span = self, "Executor.run", "executor/"
                    kind, wd_tag, key_parts = "Executor program", (), ()
                    program = program or default_main_program()
                else:
                    owner, site, span = (compiled, "CompiledProgram._run",
                                         "compiled_program/")
                    kind, wd_tag = "CompiledProgram", ("mesh",)
                    if compiled._mesh is None:
                        compiled.with_data_parallel()
                    key_parts = compiled._key_parts()
                    program = compiled._program
                feed = feed or {}
                fetch_list = list(fetch_list or [])
                scope = scope or _scope()
                fetch_names = [f.name if isinstance(f, Variable) else f
                               for f in fetch_list]
                feed_vals = owner._convert_feeds(program.global_block(), feed)
                feed_sig = feed_signature(feed_vals)
                sig = _sig_digest(feed_sig)

            with trace_span("executor/state_in"):
                state_names = self._state_names(program, scope)
                out_state_names = sorted({v.name for v in program.list_vars()
                                          if v.persistable})
                key_sig = (id(program), program._version, feed_sig,
                           tuple(fetch_names), tuple(state_names), *key_parts)
                fn = owner._cache.get(key_sig)
                compiling = fn is None
                if compiling:
                    _CACHE_MISSES.inc()
                    # every cache miss is one XLA trace+compile: count it per
                    # program and let the watchdog diagnose shape-churn storms
                    wd_key = (id(program), program._version, *wd_tag,
                              tuple(fetch_names))
                    if _WATCHDOG.record_compile(
                            wd_key, feed_sig,
                            label=f"{kind} 0x{id(program):x}"):
                        weakref.finalize(program, _WATCHDOG.forget, wd_key)
                    if compiled is None:
                        fn = self._build(program, sorted(feed_vals),
                                         fetch_names, state_names,
                                         out_state_names)
                    else:
                        fn = compiled._build(
                            sorted(feed_vals), fetch_names, state_names,
                            out_state_names,
                            {n: np.ndim(v) for n, v in feed_vals.items()})
                    owner._cache[key_sig] = fn
                else:
                    _CACHE_HITS.inc()
                state, key = owner._state_in(program, scope, state_names)

            with _FLIGHT.guard(site, program=f"0x{id(program):x}", sig=sig,
                               compiling=compiling), \
                    trace_span(span + ("compile+run" if compiling else "run"),
                               sig=sig) as call:
                # chaos probe: one hit per training-step dispatch
                # (exec.dispatch:crash@7 kills exactly step 7). Inside the
                # timed region on purpose — a delay_ms fault here IS a slow
                # step, so the StepProfiler's straggler detector must see it
                fault_point("exec.dispatch")
                fetches, new_state, new_key = fn(state, feed_vals, key)
            dt_ms = call.dur_ms

            with trace_span("executor/telemetry"):    # the instrument, timed
                # under a mesh, on a compile, also the state footprint, once
                # per signature: the number ShardingStrategy shrinks
                _record_dispatch(
                    program, sig, fn, dt_ms, compiling, feed=feed_vals,
                    new_state=new_state if compiled is not None else None)

            with trace_span("executor/state_out"):
                for n, v in new_state.items():
                    scope.set_var(n, v)
                scope.set_var(_RNG_STATE, new_key)

            with trace_span("executor/epilogue"):
                # maintenance epilogues (e.g. the deferred-row fold program,
                # optimizer.py _build_deferred_fold — pserver communicator-
                # cadence analog): run attached programs every `every` runs
                # of this program. Under the mesh too — the fold is cadence-
                # critical (the append log overflows silently if it never
                # runs)
                self._advance_epilogues(program, scope, 1, compiled=compiled)

                from ..flags import flag
                if flag("check_nan_inf"):
                    # validate every fetched value and updated state var on
                    # device; the host pays one scalar readback unless it
                    # trips
                    _check_finite(list(zip(fetch_names, fetches))
                                  + list(new_state.items()))

            if return_handle:
                # fetch-less steps still need something to block on for
                # in-flight bounding. Don't hold a new-state leaf directly:
                # the NEXT step donates those buffers, which would invalidate
                # the probe. A tiny dependent slice dispatched now lives in
                # its own buffer and completes only after this step does.
                probe = None
                if not fetches:
                    leaf = next(iter(new_state.values()), None)
                    if leaf is not None:
                        probe = jnp.ravel(leaf)[:1]
                return FetchHandle(fetch_names, fetches, probe=probe)
            if return_numpy:
                with trace_span("executor/fetch"):    # waits for the device
                    return [np.asarray(f) for f in fetches]
            return list(fetches)

    def run_batched(
        self,
        program: Program,
        feed_list,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        """Run N training steps in ONE device dispatch: lax.scan over the
        jitted step with the N feed dicts stacked along a leading axis.

        The TPU analog of the reference's in-C++ trainer hot loop
        (hogwild_worker.cc:163 via Executor::RunFromDataset): there the
        per-step loop never re-enters Python; here the per-dispatch
        host cost is paid once per N steps instead of per step. Feeds
        must share shapes/dtypes across the N steps (one compiled scan).

        Requires every persistable the program writes to already exist in
        the scope (run the startup program and one plain `run` first).
        Maintenance epilogues (deferred-row folds) keep their cadence:
        N must divide the epilogue interval (or be a multiple of it is
        rejected — the log would overflow mid-scan).

        Returns one stacked np/jax array of shape [N, ...] per fetch.

        Also accepts a CompiledProgram: the scan carry then keeps the
        compiled mesh layout — ZeRO-sharded optimizer state stays sharded
        across all N steps (donated, no per-step relayout) and feeds shard
        over the data axis per step.
        """
        from .compiler import CompiledProgram

        compiled = program if isinstance(program, CompiledProgram) else None
        if compiled is not None:
            if compiled._mesh is None:
                compiled.with_data_parallel()
            program = compiled._program

        feed_list = list(feed_list)
        if not feed_list:
            raise ValueError("run_batched: empty feed_list")
        n = len(feed_list)
        fetch_list = list(fetch_list or [])
        scope = scope or _scope()
        fetch_names = [f.name if isinstance(f, Variable) else f for f in fetch_list]
        block = program.global_block()
        keys0 = set(feed_list[0])
        for i, fd in enumerate(feed_list[1:], start=1):
            if set(fd) != keys0:
                extra = sorted(set(fd) - keys0)
                lacking = sorted(keys0 - set(fd))
                raise ValueError(
                    f"run_batched: feed dict at step {i} does not match "
                    f"step 0's key set"
                    + (f"; extra keys {extra}" if extra else "")
                    + (f"; missing keys {lacking}" if lacking else ""))
        feeds_conv = [{k: convert_feed_value(block, k, v) for k, v in fd.items()}
                      for fd in feed_list]
        keys = sorted(feeds_conv[0])
        stacked = {k: jnp.stack([jnp.asarray(fd[k]) for fd in feeds_conv])
                   for k in keys}
        return self._run_scan(program, compiled, stacked, n, fetch_names,
                              scope, return_numpy)

    def _run_scan(self, program, compiled, stacked, n, fetch_names, scope,
                  return_numpy, site="Executor.run_batched"):
        """Dispatch one ON-DEVICE scan of `n` steps over pre-stacked feeds.

        The shared engine behind `run_batched` (host-stacked feed lists)
        and `train_scanned` (DeviceLoader-staged K-step buffers): compiles
        `lax.scan` over the jitted step once per (program, n, signature),
        donates the carried state, and reports ONE aggregate profiler
        record per drain — no Python, no h2d sync, and no per-step gauge
        sampling inside the loop body.
        """
        import jax as _jax
        from jax import lax as _lax

        epilogues = getattr(program, "_epilogue_programs", None) or []
        for every, *_rest in epilogues:
            if n > every:
                raise ValueError(
                    f"{site}: {n} steps per dispatch exceeds the "
                    f"maintenance-epilogue interval {every} — the "
                    f"deferred-update log would overflow mid-scan")
        if epilogues:
            # a fold is a pure representation change (safe any time):
            # run it early if this batch would not fit in the log
            for i, entry in enumerate(epilogues):
                every, eprog, meta = (entry if len(entry) == 3
                                      else (*entry, None))
                pend, key, _ = self._epilogue_pending(program, scope, i, meta)
                if pend[key] + n > every:
                    self._run_epilogue(eprog, scope, compiled)
                    pend[key] = 0
        keys = sorted(stacked)

        state_names = sorted({v.name for v in program.list_vars()
                              if v.persistable})
        missing = [nm for nm in state_names if scope.find_var(nm) is None]
        if missing:
            raise ValueError(
                f"{site} needs every persistable in scope (run the "
                f"startup program and one plain run first); missing: "
                f"{missing[:5]}")
        stacked_sig = feed_signature(stacked)
        key_sig = (id(program), program._version, n,
                   stacked_sig, tuple(fetch_names),
                   compiled._key_parts() if compiled is not None else None)
        fn = self._cache.get(key_sig)
        compiling = fn is None
        if compiling:
            _CACHE_MISSES.inc()
            if _WATCHDOG.record_compile(
                    (id(program), program._version, "batched",
                     tuple(fetch_names)),
                    stacked_sig,
                    label=f"Executor program 0x{id(program):x} (batched)"):
                weakref.finalize(
                    program, _WATCHDOG.forget,
                    (id(program), program._version, "batched",
                     tuple(fetch_names)))
            raw_step = (self._make_step(program, fetch_names, state_names)
                        if compiled is None
                        else compiled._make_step(fetch_names, state_names))

            def scan_fn(state, feeds, key):
                def body(carry, feed):
                    st, k = carry
                    fetches, new_state, k2 = raw_step(st, feed, k)
                    return (new_state, k2), fetches
                (st, k2), ys = _lax.scan(body, (state, key), feeds)
                return ys, st, k2

            scan_fn.__name__ = _scopes.scheme_name("scan", program)

            if compiled is not None:
                # pin the scan carry to the compiled layout: ZeRO-sharded
                # state enters sharded, is donated, and leaves sharded —
                # no relayout between dispatches; stacked feeds shard over
                # the data axis in their per-step dims
                from jax.sharding import NamedSharding as _NS, \
                    PartitionSpec as _P
                mesh = compiled._mesh
                repl = _NS(mesh, _P())
                state_sh = {nm: compiled._state_sharding(nm)
                            for nm in state_names}
                feed_sh = {
                    k: compiled._stacked_feed_sharding(stacked[k].ndim)
                    for k in keys}
                fn = _jax.jit(
                    scan_fn,
                    in_shardings=(state_sh, feed_sh, repl),
                    out_shardings=([repl for _ in fetch_names],
                                   state_sh, repl),
                    donate_argnums=(0,))
            else:
                fn = _jax.jit(scan_fn, donate_argnums=(0,))
            self._cache[key_sig] = fn
        else:
            _CACHE_HITS.inc()

        pads = compiled._zero_pad_map() if compiled is not None else {}
        zero_pads = getattr(program, "_zero_padded", None) or {}
        state = {}
        for nm in state_names:
            v = scope.find_var(nm)
            pad = pads.get(nm)
            if (pad is not None and getattr(v, "shape", None)
                    and v.shape[0] == pad[0]):
                # logical-shape value headed for a padded ZeRO boundary
                arr = np.asarray(v)
                v = np.pad(arr, [(0, pad[1] - pad[0])]
                           + [(0, 0)] * (arr.ndim - 1))
            elif (compiled is None and nm in zero_pads
                  and getattr(v, "shape", None)
                  and zero_pads[nm] and v.shape[0] > zero_pads[nm][0]):
                # inverse: padded scope value entering an unsharded scan
                v = jnp.asarray(v)[:zero_pads[nm][0]]
            if isinstance(v, jax.Array):
                state[nm] = v
            elif compiled is not None:
                # host value: place straight into the compiled layout so a
                # ZeRO shard never materializes fully replicated
                try:
                    state[nm] = jax.device_put(
                        v, compiled._state_sharding(nm))
                except (TypeError, ValueError):
                    state[nm] = jnp.asarray(v)
            else:
                state[nm] = jnp.asarray(v)
        key = scope.find_var(_RNG_STATE)
        if key is None:
            key = _make_key(program.random_seed or 0)
        avals = per_step_feed = None
        if compiling:
            # what the perf ledger asks for once the call has donated
            # `state`: the avals to lower on again, and one step's feed for
            # the analytic fallback, which scales one IR-walk step by n
            avals = _avals_of((state, stacked, key))
            per_step_feed = {
                k: SimpleNamespace(
                    shape=tuple(v.shape[1:]),
                    nbytes=int(getattr(v, "nbytes", 0)) // max(n, 1))
                for k, v in stacked.items()}
        sig = _sig_digest(stacked_sig)
        with _FLIGHT.guard(site, program=f"0x{id(program):x}", sig=sig,
                           steps=n, compiling=compiling), \
                trace_span(site.replace("Executor.", "executor/"), steps=n,
                           sig=sig) as call:
            if compiling:
                with _setup.staging("call"), _setup.phase(
                        "first_run", getattr(fn, "__name__", "scan")):
                    ys, new_state, new_key = fn(state, stacked, key)
            else:
                ys, new_state, new_key = fn(state, stacked, key)
        dt_ms = call.dur_ms
        with trace_span("executor/telemetry"):
            # the cost entry covers the whole n-step dispatch
            _record_dispatch(
                program, sig, fn, dt_ms, compiling, feed=per_step_feed,
                avals=avals, steps=n,
                new_state=new_state if compiled is not None else None)
        for nm, v in new_state.items():
            scope.set_var(nm, v)
        scope.set_var(_RNG_STATE, new_key)

        self._advance_epilogues(program, scope, n, compiled=compiled)
        if return_numpy:
            return [np.asarray(y) for y in ys]
        return list(ys)

    def _epilogue_pending(self, program, scope, i, meta):
        """Steps-since-fold for epilogue i of `program` against `scope`.

        Kept ON THE SCOPE (the deferred log/count state lives there — one
        program driven against two scopes must not share a counter), and
        seeded from the scope's in-program count vars on first encounter,
        so a checkpoint-restored scope resumes with the correct cadence
        without a per-step device sync."""
        pend = getattr(scope, "_epilogue_pending", None)
        if pend is None:
            pend = scope._epilogue_pending = {}
        key = (id(program), i)
        fresh = key not in pend
        if fresh:
            seed = 0
            r = int((meta or {}).get("rows_per_step", 0))
            for nm in (meta or {}).get("count_vars", []):
                v = scope.find_var(nm)
                if v is not None and r > 0:
                    seed = max(seed,
                               int(np.asarray(v).reshape(-1)[0]) // r)
            pend[key] = seed
            # id(program) recycles after GC — purge this program's counters
            # when it dies so a new program at the same address cannot
            # alias a stale steps-since-fold count
            weakref.finalize(program, _purge_pending, pend, id(program))
        return pend, key, fresh

    def _run_epilogue(self, eprog, scope, compiled=None):
        if compiled is not None and compiled._mesh is not None:
            from .compiler import CompiledProgram
            cache = getattr(compiled, "_compiled_epilogues", None)
            if cache is None:
                cache = compiled._compiled_epilogues = {}
            cp = cache.get(id(eprog))
            if cp is None:
                cp = CompiledProgram(eprog).with_mesh(
                    compiled._mesh, data_axis=compiled._data_axis)
                cache[id(eprog)] = cp
                # same id-reuse hazard as the fold counters: drop the
                # compiled epilogue when its program dies
                weakref.finalize(eprog, cache.pop, id(eprog), None)
            eprog = cp
        self.run(eprog, scope=scope, return_numpy=False)

    def _advance_epilogues(self, program, scope, steps: int, compiled=None):
        """Track steps since each epilogue last ran; fire at its interval.
        The accounting mirrors the in-program deferred-log `count` state:
        both reset together when the fold runs."""
        epilogues = getattr(program, "_epilogue_programs", None)
        if not epilogues:
            return
        for i, entry in enumerate(epilogues):
            every, eprog, meta = (entry if len(entry) == 3
                                  else (*entry, None))
            pend, key, fresh = self._epilogue_pending(program, scope, i,
                                                      meta)
            if not fresh:
                # a fresh seed read the in-program count AFTER this run's
                # append — it already includes these steps
                pend[key] += steps
            if pend[key] >= every:
                self._run_epilogue(eprog, scope, compiled)
                pend[key] = 0

    def train_scanned(self, program=None, reader=None, scan_steps: int = 16,
                      fetch_list=None, scope=None, capacity=None):
        """On-device training driver: the whole epoch runs as K-step
        `lax.scan` dispatches with ZERO per-step Python.

        The full TPU analog of the reference's in-C++ trainer loop
        (Executor::RunFromDataset → hogwild_worker.cc:163): the host's
        only jobs are feeding batches through `DeviceLoader`'s prefetch
        queue — pre-staged into a device-resident K-step feed buffer via
        `peek_many` — and draining scalar fetches once per K steps. Step
        compute, the optimizer, and the RNG walk all stay inside one
        compiled scan; the profiler sees one aggregate record per drain
        (wall/K = per-step time), and the flight recorder one
        `Executor.train_scanned` dispatch site with `steps=K`.

        reader: callable returning an iterable of feed dicts, or a plain
          iterable (one epoch). Feeds must share shapes/dtypes.
        scan_steps: K, the steps fused per dispatch. Metrics/losses are
          only observable at K-step granularity; with deferred-row
          epilogues K must not exceed the fold cadence. A short final
          drain (epoch length not divisible by K) compiles one extra
          scan length.
        capacity: DeviceLoader queue depth (default max(2, K)).

        Accepts a CompiledProgram (state stays in the compiled layout
        across drains, donated between them). Requires every persistable
        in scope — run the startup program and one plain `run` first.

        Returns a list of per-fetch np arrays of shape [num_steps, ...]
        (all drains concatenated), or the step count when `fetch_list`
        is empty.
        """
        from .compiler import CompiledProgram
        from ..dataio.loader import DeviceLoader

        program = program or default_main_program()
        compiled = program if isinstance(program, CompiledProgram) else None
        if compiled is not None:
            if compiled._mesh is None:
                compiled.with_data_parallel()
            program = compiled._program
        if reader is None:
            raise ValueError("train_scanned: a reader (callable returning "
                             "an iterable of feed dicts) is required")
        k = int(scan_steps)
        if k < 1:
            raise ValueError(f"train_scanned: scan_steps must be >= 1, "
                             f"got {scan_steps}")
        fetch_list = list(fetch_list or [])
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in fetch_list]
        scope = scope or _scope()
        loader = DeviceLoader(reader,
                              capacity=max(2, capacity or k),
                              program=program, name="train_scanned")
        self._loaders.add(loader)
        loader.start()
        drains = []
        pending = None  # keep ONE drain's fetches un-synced behind dispatch
        total = 0
        try:
            while True:
                stacked, m = loader.peek_many(k)
                if m == 0:
                    break
                ys = self._run_scan(program, compiled, stacked, m,
                                    fetch_names, scope, return_numpy=False,
                                    site="Executor.train_scanned")
                total += m
                if pending is not None:
                    drains.append([np.asarray(y) for y in pending])
                pending = ys
        finally:
            loader.close()
            self._loaders.discard(loader)
        if pending is not None:
            drains.append([np.asarray(y) for y in pending])
        if not fetch_names:
            return total
        return [np.concatenate([d[i] for d in drains], axis=0)
                for i in range(len(fetch_names))]

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None, print_period: int = 100):
        """Dataset-driven training loop (reference executor.py:894 →
        Executor::RunFromDataset → MultiTrainer N-thread hot loop,
        hogwild_worker.cc:163). TPU-native, fully pipelined: a
        DeviceLoader worker converts and device_puts batch N+1 while the
        device runs step N (buffered_reader.cc role), and up to
        ``max_inflight_steps`` (flags.py; env PDTPU_MAX_INFLIGHT_STEPS,
        default 2) dispatches stay un-synced so jax's async dispatch
        queues compute behind host work instead of serializing on a
        per-step fetch."""
        program = program or default_main_program()
        fetch_list = list(fetch_list or [])
        if dataset is None:
            raise ValueError("dataset is required")
        if thread:
            dataset.set_thread(thread)
        from ..dataio.loader import DeviceLoader
        from ..flags import flag

        max_inflight = max(1, int(flag("max_inflight_steps")))
        block = program.global_block()
        names = fetch_info or [getattr(f, "name", str(f))
                               for f in fetch_list]

        def batches():
            for batch in dataset.batches():
                yield {k: v for k, v in batch.items()
                       if block._find_var_recursive(k) is not None}

        inflight: "collections.deque" = collections.deque()

        def retire(entry):
            step_i, handle = entry
            if debug and fetch_list and step_i % print_period == 0:
                vals = handle.numpy()
                print(f"step {step_i}: " + ", ".join(
                    f"{n}={np.asarray(v).mean():.6f}"
                    for n, v in zip(names, vals)))
            else:
                handle.block_until_ready()

        loader = DeviceLoader(batches, capacity=max(2, max_inflight),
                              program=program, name="train_from_dataset")
        self._loaders.add(loader)
        step = 0
        last = None
        try:
            for feed in loader:
                last = self.run(program, feed=feed, fetch_list=fetch_list,
                                scope=scope, return_handle=True)
                inflight.append((step, last))
                _INFLIGHT.set(len(inflight))
                while len(inflight) > max_inflight:
                    retire(inflight.popleft())
                    _INFLIGHT.set(len(inflight))
                step += 1
            while inflight:
                retire(inflight.popleft())
                _INFLIGHT.set(len(inflight))
        finally:
            _INFLIGHT.set(0)
            loader.close()
        return last.numpy() if last is not None else None

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None, print_period: int = 100):
        """executor.py:817 parity — same loop on a for_test program."""
        program = (program or default_main_program()).clone(for_test=True)
        return self.train_from_dataset(program, dataset, scope, thread, debug,
                                       fetch_list, fetch_info, print_period)

    def close(self):
        # tear down any prefetch workers this executor spun up (they hold
        # queued device batches) before dropping the executable cache
        for ld in list(self._loaders):
            ld.close()
        self._cache.clear()
