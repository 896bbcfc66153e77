"""CompiledProgram — multi-device execution of a Program via GSPMD/pjit.

Reference analog: ``python/paddle/fluid/compiler.py:65`` (CompiledProgram,
with_data_parallel:143) backed by the C++ ParallelExecutor
(parallel_executor.cc:356) + multi-device SSA graph passes that clone ops per
GPU and insert NCCL AllReduceOpHandles per gradient
(multi_devices_graph_pass.cc:454).

TPU-native redesign: none of that graph surgery exists here. Data parallelism
is expressed by sharding the *feed* batch across a `jax.sharding.Mesh` data
axis and replicating state; XLA's SPMD partitioner then emits the ICI
all-reduce for gradients automatically — the whole AllReduce/Reduce/fused-
allreduce pass pipeline (build_strategy.cc:46-235) collapses into sharding
annotations. Tensor-parallel parameters opt in via `Parameter.shard_spec`.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import jax.numpy as jnp

from .executor import (_RNG_STATE, _Step, _make_key, _make_step,
                       convert_feed_value)
from .program import Program


class ShardingStrategy:
    """ZeRO-style sharding of model state over the data-parallel mesh axis
    (Rajbhandari et al. 2020, expressed as GSPMD sharding annotations per
    Xu et al. 2021 — XLA lowers the annotations to reduce-scatter +
    all-gather, no manual collectives).

    - ``off``    — every state leaf replicated on every device (legacy).
    - ``stage1`` — optimizer accumulators and master weights shard over the
      dp axis: per-device state bytes drop by ~1/dp.
    - ``stage2`` — stage1 plus gradients constrained to the same layout at
      trace time, so persistent gradient buffers (GradientMergeOptimizer's
      ``@GradientMerge`` accumulators) shard too and XLA reduce-scatters
      instead of all-reducing into a replicated buffer.
    - ``stage3`` — stage2 plus the PARAMETERS themselves (full-parameter
      FSDP / ZeRO-3): each float parameter leaf shards over dp along its
      largest dp-divisible dim (same padded-boundary fallback as the
      optimizer state), lives sharded between steps, and is re-asserted
      sharded inside the step via `with_sharding_constraint` so XLA emits
      an all-gather at each USE site and overlaps the gathers with
      compute. Per-device state bytes for params+grads+accumulators all
      drop ~1/dp; losses stay identical — sharding only relays where each
      element lives. TP parameters (`shard_spec`) keep their own layout.
    """

    off = 0
    stage1 = 1
    stage2 = 2
    stage3 = 3
    # CamelCase aliases matching ReduceStrategy naming
    Off = off
    Stage1 = stage1
    Stage2 = stage2
    Stage3 = stage3


def _zero_axis(shape, dp: int) -> Optional[int]:
    """Pick the dim of `shape` to shard over a dp-sized axis: the largest
    dp-divisible dim, else dim 0 when it is at least dp long (GSPMD pads
    the ragged last shards, per-device extent ⌈shape[0]/dp⌉). None means
    the leaf stays replicated (scalars, tiny leaves)."""
    dims = [d if isinstance(d, int) else -1 for d in (shape or ())]
    divisible = [i for i, d in enumerate(dims) if d > 0 and d % dp == 0]
    if divisible:
        return max(divisible, key=lambda i: dims[i])
    if dims and dims[0] >= dp:
        return 0
    return None


# Cheap-to-recompute op types: big activation residuals, trivial FLOPs to
# rebuild. The "minimal" remat policy checkpoints exactly these (outside
# annotated units), matching the reference RecomputeOptimizer's default of
# recomputing activations but never matmuls.
_MINIMAL_REMAT_OPS = frozenset({
    "relu", "gelu", "tanh", "sigmoid", "softmax", "dropout", "layer_norm",
    "batch_norm", "elementwise_add", "elementwise_mul", "scale",
})


class RematSpec:
    """Resolved remat policy — what the trace actually does.

    - ``op_set``: per-op jax.checkpoint outside remat units — False (off),
      True (every differentiable op), or a frozenset of op types.
    - ``unit_policy``: None (no unit grouping) or a callable
      ``unit_name -> False | True | "minimal" | "full"`` deciding whether a
      `fluid.remat_unit(...)` block is wrapped in one jax.checkpoint —
      "minimal" keeps matmul outputs (`jax.checkpoint_policies.
      dots_saveable`), "full"/True saves nothing (max HBM savings).
    - ``saveable_names``: the values a unit keeps as residuals, mapped onto
      `save_only_these_names`; everything else in the unit recomputes. A
      tuple of names (the caller's: every unit) or a dict unit path -> names
      (the program's own `remat_keep`), or None.
    - ``token``: hashable identity for executable cache keys.
    """

    __slots__ = ("op_set", "unit_policy", "saveable_names", "token")

    def __init__(self, op_set, unit_policy, saveable_names, token):
        self.op_set = op_set
        self.unit_policy = unit_policy
        self.saveable_names = saveable_names
        self.token = token

    def names_for(self, unit):
        """The names of the values remat block `unit` (its path) keeps."""
        names = self.saveable_names
        if isinstance(names, dict):
            names = names.get(unit)
        return tuple(names or ())

    def jax_policy(self, unit_decision, unit):
        """jax.checkpoint `policy=` for remat block `unit` and its decision."""
        names = self.names_for(unit)
        if names:
            return jax.checkpoint_policies.save_only_these_names(*names)
        if unit_decision == "minimal":
            return jax.checkpoint_policies.dots_saveable
        return None  # "full"/True: save nothing, recompute the whole unit


REMAT_POLICIES = ("none", "minimal", "full")


def resolve_remat(policy=None, legacy_remat=False, saveable_names=None,
                  program=None):
    """Map the remat policy surface (BuildStrategy.remat_policy /
    DistributedStrategy.remat_policy / legacy boolean-or-set
    BuildStrategy.remat) onto a RematSpec. Where none of them gives a
    policy, `program.remat_policy` (what the model's builder asked for its
    own remat units) is taken, and with it, where the caller names no values
    either, what the builder said those units keep (`program.remat_keep`)."""
    names = tuple(saveable_names) if saveable_names else None
    if policy is None and program is not None:
        policy = getattr(program, "remat_policy", None)
        keep = getattr(program, "remat_keep", None)
        if policy is not None and names is None and keep:
            names = {unit: tuple(ns) for unit, ns in keep.items()}
    # the names' part of the token: a dict does not hash
    tok = tuple(sorted(names.items())) if isinstance(names, dict) else names
    if policy is None:
        # legacy knob: True = per-op checkpoint everywhere, a set = only
        # those op types; no unit grouping (exact pre-policy behavior)
        if legacy_remat is True:
            return RematSpec(True, None, names, ("legacy", True, tok))
        if isinstance(legacy_remat, (set, frozenset)) and legacy_remat:
            fs = frozenset(legacy_remat)
            return RematSpec(fs, None, names,
                             ("legacy", tuple(sorted(fs)), tok))
        return RematSpec(False, None, None, ("none",))
    if callable(policy):
        # per-layer predicate: unit_name -> False | True | "minimal" | "full"
        return RematSpec(False, policy, names,
                         ("predicate", id(policy), tok))
    p = str(policy)
    if p == "none":
        return RematSpec(False, None, None, ("none",))
    if p == "minimal":
        return RematSpec(frozenset(_MINIMAL_REMAT_OPS),
                         lambda unit: "minimal", names, ("minimal", tok))
    if p == "full":
        return RematSpec(True, lambda unit: "full", names, ("full", tok))
    raise ValueError(
        f"remat_policy must be one of {REMAT_POLICIES}, a per-layer "
        f"predicate (unit_name -> bool|'minimal'|'full'), or None for the "
        f"legacy BuildStrategy.remat knob — got {policy!r}")


class BuildStrategy:
    """Knob bag kept for API parity (reference build_strategy.h:37-186).
    Most knobs are no-ops on TPU — XLA owns fusion and memory reuse. The ones
    that matter map to sharding/remat choices."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.fuse_elewise_add_act_ops = True   # XLA fuses anyway
        self.fuse_all_reduce_ops = True
        self.fuse_all_optimizer_ops = True
        self.memory_optimize = True
        self.enable_inplace = True
        self.remat = False                     # legacy: True | {op types}
        # remat policy surface: "none" | "minimal" | "full" | callable
        # (unit_name -> bool|"minimal"|"full"); None defers to the legacy
        # `remat` knob. See resolve_remat().
        self.remat_policy = None
        # optional var names kept as residuals inside remat units
        # (jax.checkpoint_policies.save_only_these_names)
        self.remat_saveable_names = None
        self.sharding_strategy = ShardingStrategy.off
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    """reference execution_strategy.h:22 — scheduling knobs; XLA schedules."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = False


class CompiledProgram:
    def __init__(self, program: Program):
        self._program = program
        self._mesh: Optional[Mesh] = None
        self._data_axis: Optional[str] = None
        self._seq_axis: Optional[str] = None
        self._cache: Dict = {}
        self.build_strategy: Optional[BuildStrategy] = None
        self.exec_strategy: Optional[ExecutionStrategy] = None

    # -- configuration -----------------------------------------------------
    def with_data_parallel(self, loss_name: Optional[str] = None,
                           build_strategy: Optional[BuildStrategy] = None,
                           exec_strategy: Optional[ExecutionStrategy] = None,
                           places: Optional[Sequence] = None,
                           share_vars_from=None):
        """Shard the batch over every visible device (compiler.py:143 parity)."""
        devices = list(places) if places and not isinstance(places[0], int) else None
        n = len(places) if places is not None else len(jax.devices())
        devs = np.array(jax.devices()[:n]) if devices is None else np.array(devices)
        self._mesh = Mesh(devs, ("dp",))
        self._data_axis = "dp"
        self.build_strategy = build_strategy or BuildStrategy()
        self.exec_strategy = exec_strategy or ExecutionStrategy()
        return self

    def with_mesh(self, mesh: Mesh, data_axis: Optional[str] = "dp",
                  strategy=None, seq_axis: Optional[str] = None):
        """TPU-native extension: run over an arbitrary (dp, mp, pp, sp) mesh.
        Parameters carrying `shard_spec` are placed accordingly (Megatron-style
        TP); everything else is replicated. `strategy` (a fleet
        DistributedStrategy) wires sharding_degree (ZeRO optimizer-state
        sharding over the data axis) and recompute (remat).

        ``seq_axis``: shard dim 1 (the sequence dim) of every rank≥2 feed
        over this mesh axis — GSPMD sequence parallelism: embeddings,
        layer norms, dropout and the FFN stay sequence-sharded and XLA
        inserts the gathers attention needs (the annotation-only form of
        Megatron-SP; the ring-attention kernels are the manual form)."""
        self._mesh = mesh
        self._data_axis = data_axis if data_axis in mesh.axis_names else None
        self._seq_axis = seq_axis if seq_axis in mesh.axis_names else None
        if (self._seq_axis is not None
                and self._seq_axis == self._data_axis):
            raise ValueError(
                f"with_mesh: seq_axis and data_axis are both "
                f"{seq_axis!r} — a feed dim cannot shard over the same "
                f"mesh axis twice; use distinct axes")
        self._strategy_stage = 0       # re-derived per call, never sticky
        self._strategy_remat = False   # ditto; build_strategy.remat is the
        self._strategy_remat_policy = None  # user's own knob, left alone
        if strategy is not None:
            if getattr(strategy, "sharding_degree", 1) > 1:
                # sharding on; sharding_stage picks ZeRO-1/2/3
                self._strategy_stage = max(
                    1, int(getattr(strategy, "sharding_stage", 1) or 1))
            if getattr(strategy, "recompute", False):
                self._strategy_remat = True
            self._strategy_remat_policy = getattr(
                strategy, "remat_policy", None)
            if getattr(strategy, "gradient_merge_steps", 1) > 1:
                raise NotImplementedError(
                    "gradient_merge_steps on DistributedStrategy is not "
                    "wired; use fluid.optimizer.GradientMergeOptimizer")
        return self

    def with_inference_optimize(self, config=None):
        """Reference inference_optimize parity: freeze to the test-mode
        graph, and when an inference `Config` is supplied run its IR
        pass pipeline (the same compile-then-serve path the Predictor
        takes) with per-pass cost deltas recorded in the perf ledger."""
        self._program = self._program.clone(for_test=True)
        if config is not None and getattr(config, "ir_optim", lambda: False)():
            from ..ir.pipeline import optimize_inference_program
            self._program = optimize_inference_program(
                self._program, config,
                label=f"compiled:0x{id(self._program):x}")
        return self

    # -- lowering ----------------------------------------------------------
    def _zero_stage(self) -> int:
        """Effective ShardingStrategy stage: the stronger of the fleet
        DistributedStrategy wiring (with_mesh) and build_strategy's own
        knob, resolved lazily so `c.build_strategy = bs` after
        with_data_parallel/with_mesh still takes effect."""
        if self._data_axis is None or self._mesh is None:
            return ShardingStrategy.off
        stage = int(getattr(self, "_strategy_stage", 0) or 0)
        bs = self.build_strategy
        if bs is not None:
            stage = max(stage, int(getattr(bs, "sharding_strategy", 0) or 0))
        return stage

    def _remat_spec(self) -> RematSpec:
        """Effective remat policy, resolved lazily (same contract as
        _zero_stage): build_strategy.remat_policy wins, then the fleet
        DistributedStrategy's remat_policy, then the legacy boolean/set
        knobs (build_strategy.remat, DistributedStrategy.recompute)."""
        bs = self.build_strategy
        policy = getattr(bs, "remat_policy", None) if bs is not None else None
        if policy is None:
            policy = getattr(self, "_strategy_remat_policy", None)
        legacy = ((bs.remat if bs is not None else False)
                  or getattr(self, "_strategy_remat", False))
        names = (getattr(bs, "remat_saveable_names", None)
                 if bs is not None else None)
        return resolve_remat(policy, legacy, names, self._program)

    def _zero_plan(self, var):
        """(axis, pad_to) sharding plan for `var` over the data axis under
        the effective ZeRO stage, or None to leave it replicated. Eligible
        leaves — optimizer accumulators, master weights, and (stage2)
        persistent gradient buffers, all tagged at creation so this is
        robust against naming schemes — shard along their largest
        dp-divisible dim; the dim-0 fallback (see _zero_axis) pads the
        BOUNDARY representation to ⌈d/dp⌉·dp (pad_to), because jax requires
        jit argument/result shardings to divide evenly — the step slices
        the pad off on entry and re-pads on exit (_make_step)."""
        stage = self._zero_stage()
        if stage < ShardingStrategy.stage1 or var is None:
            return None
        shardable = (getattr(var, "is_optimizer_state", False)
                     or getattr(var, "is_master_weight", False)
                     or (stage >= ShardingStrategy.stage2
                         and getattr(var, "is_grad_buffer", False))
                     or (stage >= ShardingStrategy.stage3
                         and self._fsdp_param(var)))
        if not shardable or not getattr(var, "zero_shardable", True):
            return None
        dp = self._mesh.shape[self._data_axis]
        axis = _zero_axis(var.shape, dp)
        if axis is None:
            return None
        d = var.shape[axis]
        pad_to = None if d % dp == 0 else -(-d // dp) * dp
        return axis, pad_to

    @staticmethod
    def _fsdp_param(var) -> bool:
        """Stage3 eligibility: trainable float parameters without a TP
        `shard_spec` (TP owns those layouts). Non-float leaves (e.g.
        row-packed uint16 embedding tables, driven by custom scatter
        kernels) stay replicated — FSDP'ing them buys little and their
        update paths assume a whole table."""
        if not (getattr(var, "trainable", False) and var.persistable):
            return False
        if getattr(var, "shard_spec", None) is not None:
            return False
        from .dtypes import dtype_str
        try:
            return dtype_str(var.dtype) in ("float32", "float64", "float16",
                                            "bfloat16")
        except Exception:
            return False

    def _zero_pspec(self, var) -> Optional[P]:
        plan = self._zero_plan(var)
        if plan is None:
            return None
        return P(*([None] * plan[0]), self._data_axis)

    def _zero_pad_map(self):
        """{name: (logical_dim0, padded_dim0)} for every persistable on the
        padding fallback under the current mesh/stage. Also recorded on the
        Program (`_zero_padded`: name -> logical shape) so layout-unaware
        paths (plain Executor, checkpoint save) can slice the pad off a
        scope value that last crossed a sharded boundary."""
        pads = {}
        for v in self._program.list_vars():
            if not v.persistable:
                continue
            plan = self._zero_plan(v)
            if plan is not None and plan[1] is not None:
                pads[v.name] = (v.shape[0], plan[1])
        if pads:
            rec = getattr(self._program, "_zero_padded", None)
            if rec is None:
                rec = self._program._zero_padded = {}
            for n, (d, _) in pads.items():
                var = self._program.global_block()._find_var_recursive(n)
                rec[n] = tuple(var.shape)
        return pads

    def _state_sharding(self, name: str):
        var = self._program.global_block()._find_var_recursive(name)
        spec = getattr(var, "shard_spec", None) if var is not None else None
        if spec is None:
            # ZeRO (ShardingStrategy / DistributedStrategy.sharding_degree):
            # GSPMD inserts the reduce-scatter/all-gather, the reference's
            # sharding pass (fleet meta sharding) becomes an annotation.
            spec = self._zero_pspec(var)
            if spec is not None:
                return NamedSharding(self._mesh, spec)
            return NamedSharding(self._mesh, P())
        spec = P(*spec) if not isinstance(spec, P) else spec
        return NamedSharding(self._mesh, spec)

    def _feed_sharding(self, ndim: Optional[int] = None):
        if self._data_axis is None and getattr(self, "_seq_axis", None) is None:
            return NamedSharding(self._mesh, P())
        seq = getattr(self, "_seq_axis", None)
        if seq is not None and ndim is not None and ndim >= 2:
            return NamedSharding(self._mesh, P(self._data_axis, seq))
        return NamedSharding(self._mesh, P(self._data_axis))

    def _stacked_feed_sharding(self, ndim: Optional[int] = None):
        """Sharding for a K-step scan feed buffer ([K, ...] stacked
        per-step feeds, as built by `Executor.run_batched` /
        `DeviceLoader.peek_many`): the leading scan axis stays replicated,
        the per-step dims shard exactly as `_feed_sharding` would shard a
        single step's feed."""
        per_step = self._feed_sharding(None if ndim is None else ndim - 1)
        return NamedSharding(self._mesh, P(None, *per_step.spec))

    def _grad_shard_fn(self):
        """Stage2: trace-time hook constraining each parameter gradient to
        the ZeRO layout of its parameter, so XLA emits a reduce-scatter for
        the cross-replica sum instead of an all-reduce into a replicated
        buffer (and `@GradientMerge` accumulation stays sharded)."""
        if self._zero_stage() < ShardingStrategy.stage2:
            return None
        mesh, data_axis = self._mesh, self._data_axis
        dp = mesh.shape[data_axis]
        block = self._program.global_block()

        def shard_grad(target_name, g):
            shape = getattr(g, "shape", None)
            if shape is None or not hasattr(g, "dtype"):
                return g  # SelectedRows-style sparse grads stay untouched
            var = block._find_var_recursive(target_name)
            if var is not None and getattr(var, "shard_spec", None) is not None:
                return g  # TP parameters own their layout
            axis = _zero_axis(shape, dp)
            if axis is None:
                return g
            return jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, P(*([None] * axis), data_axis)))

        return shard_grad

    def _key_parts(self):
        """What a mesh step's cache key holds beside the program's version
        and the call's signature: everything here that changes the trace."""
        return (id(self._mesh), self._data_axis, self._zero_stage(),
                self._remat_spec().token, self._seq_axis)

    def _make_step(self, fetch_names, out_state_names):
        """The pure step (`executor._make_step`) with what the mesh adds to
        it — shared by _build and Executor._run_scan's scan carry."""
        # stage3 (FSDP): re-assert each sharded parameter's dp layout INSIDE
        # the step. in_shardings only pins the boundary; the constraint keeps
        # the resident value sharded so every USE becomes an all-gather that
        # XLA's scheduler overlaps with compute, and the weight update runs
        # on the shard.
        fsdp_sh = {}
        if self._zero_stage() >= ShardingStrategy.stage3:
            for v in self._program.list_vars():
                if v.persistable and self._fsdp_param(v):
                    pspec = self._zero_pspec(v)
                    if pspec is not None:
                        fsdp_sh[v.name] = NamedSharding(self._mesh, pspec)
        return _make_step(
            self._program, fetch_names, out_state_names, self._remat_spec(),
            mesh=self._mesh, data_axis=self._data_axis,
            shard_grad=self._grad_shard_fn(), pads=self._zero_pad_map(),
            fsdp_sh=fsdp_sh)

    def _build(self, feed_names, fetch_names, state_names, out_state_names,
               feed_ndims=None):
        mesh = self._mesh
        step = self._make_step(fetch_names, out_state_names)

        state_sh = {n: self._state_sharding(n) for n in state_names}
        feed_sh = {n: self._feed_sharding((feed_ndims or {}).get(n))
                   for n in feed_names}
        key_sh = NamedSharding(mesh, P())
        out_state_sh = {n: self._state_sharding(n) for n in out_state_names}

        # fetches are replicated so every process can np.asarray() them
        # (a partially-addressable fetch would fail on multi-host)
        fetch_sh = [NamedSharding(mesh, P()) for _ in fetch_names]
        return _Step(jax.jit(
            step,
            in_shardings=(state_sh, feed_sh, key_sh),
            out_shardings=(fetch_sh, out_state_sh, key_sh),
            donate_argnums=(0,),
        ))

    # -- what Executor.run asks of a mesh step's owner ----------------------
    def _convert_feeds(self, block, feed):
        multiproc = jax.process_count() > 1
        feed_vals = {}
        for name, val in feed.items():
            var = block._find_var_recursive(name)
            dtype = var.dtype if var is not None else None
            if multiproc and not isinstance(val, jax.Array):
                # each trainer process feeds its LOCAL batch shard (the
                # reference's per-trainer reader contract, test_dist_base.py);
                # assemble the global array across processes
                if getattr(self, "_seq_axis", None) is not None:
                    raise NotImplementedError(
                        "multi-process feeds assume batch-only sharding "
                        "(each trainer supplies its local batch rows at "
                        "FULL sequence length) — with seq_axis set the "
                        "expected per-process shape would also split the "
                        "sequence dim. Feed a pre-built global jax.Array "
                        "instead, or drop seq_axis for multi-process runs.")
                local = np.asarray(val)
                if dtype is not None:
                    local = local.astype(jnp.dtype(dtype))
                feed_vals[name] = jax.make_array_from_process_local_data(
                    self._feed_sharding(local.ndim), local)
            else:
                feed_vals[name] = convert_feed_value(block, name, val)
        return feed_vals

    def _state_in(self, program, scope, state_names):
        """The state leaves in their compiled layout, and the RNG key."""
        multiproc = jax.process_count() > 1
        pads = self._zero_pad_map()
        state = {}
        for n in state_names:
            v = scope.find_var(n)
            pad = pads.get(n)
            if (pad is not None and getattr(v, "shape", None)
                    and v.shape[0] == pad[0]):
                # logical-shape value headed for a padded boundary (startup
                # init, checkpoint restore, or a relayout from an unsharded
                # run): pad on host — these are the small non-divisible
                # leaves, the round-trip is cheap
                arr = np.asarray(v)
                v = np.pad(arr, [(0, pad[1] - pad[0])]
                           + [(0, 0)] * (arr.ndim - 1))
            if multiproc and not isinstance(v, jax.Array):
                # process-local startup values are identical across ranks
                # (same seed) and hold the FULL value; the callback slices
                # each device's shard from it, which stays correct for
                # sharded (shard_spec) parameters, unlike
                # make_array_from_process_local_data (which would treat the
                # full copy as this process's shard)
                full = np.asarray(v)
                state[n] = jax.make_array_from_callback(
                    full.shape, self._state_sharding(n),
                    lambda idx, _full=full: _full[idx])
            elif not isinstance(v, jax.Array):
                # host value (startup init or a checkpoint restore): place it
                # straight into its compiled layout, so ZeRO/TP state never
                # holds a fully-replicated transient on every device
                try:
                    state[n] = jax.device_put(v, self._state_sharding(n))
                except (TypeError, ValueError):
                    state[n] = jnp.asarray(v)
            else:
                state[n] = v
        key = scope.find_var(_RNG_STATE)
        if key is None:
            key = _make_key(program.random_seed or 0)
        if multiproc and not (isinstance(key, jax.Array)
                              and len(key.sharding.device_set) > 1):
            sh = NamedSharding(self._mesh, P())
            if jax.dtypes.issubdtype(getattr(key, "dtype", None),
                                     jax.dtypes.prng_key):
                # typed keys (rbg on TPU) can't round-trip through numpy
                impl = jax.random.key_impl(key)
                data = np.asarray(jax.random.key_data(key))
                key = jax.random.wrap_key_data(
                    jax.make_array_from_process_local_data(sh, data),
                    impl=impl)
            else:
                key = jax.make_array_from_process_local_data(
                    sh, np.asarray(key))
        return state, key
