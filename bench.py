#!/usr/bin/env python
"""Benchmark: ERNIE/BERT-base pretrain step throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no numbers (BASELINE.md), so vs_baseline is measured
MFU / the 0.35 MFU target from BASELINE.json.

`python bench.py` is a sequencer that imports no JAX: a chip belongs to one
process, so every section runs as its own `bench.py --section NAME` child,
one at a time, and the parent merges the JSON line each prints. It exits
nonzero when any section failed.
"""
import json
import os
import sys
import time

import numpy as np

def _calibration(recalibrate=False):
    """Shared chip floors (observability/calibrate.py): measured once per
    machine by the first section child, disk-cached, read by every later
    child. `bench.py --recalibrate` forces a fresh measurement. An unknown
    device kind or an empty trace on a TPU raises."""
    from paddle_tpu.observability import calibrate
    return calibrate.get_calibration(recalibrate=recalibrate)


def _device_memory_snapshot():
    """Allocator stats of device 0, or None on backends without them
    (CPU). Keys kept small and stable for the bench JSON."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size")
    return {k: int(stats[k]) for k in keep if k in stats}


def _telemetry_out(section, kind, doc):
    """Sidecar parity with serving_bench's --trace-out/--metrics-out:
    the observability-bearing sections drop their merged fleet trace and
    federated metrics snapshot as JSON files next to the bench output.
    PDTPU_BENCH_TELEMETRY_DIR overrides the default tmpdir location.
    Returns the written path (None when there is nothing to write)."""
    if doc is None:
        return None
    import tempfile

    d = (os.environ.get("PDTPU_BENCH_TELEMETRY_DIR")
         or os.path.join(tempfile.gettempdir(), "pdtpu_bench_telemetry"))
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{section}_{kind}.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


# Run order. `python bench.py` (the parent) imports no JAX: a chip belongs
# to one process, so the parent only sequences `bench.py --section NAME`
# children, one at a time, and merges the JSON line each prints. A child's
# allocator (and any OOM ceiling it hit) dies with it, and a crash costs
# only that section. Calibration is measured by the first child and read
# from its disk cache by the rest.
SECTIONS = ("bert", "resnet50", "deepfm", "dispatch_overhead", "nmt_big",
            "ring_attn", "dygraph", "input_pipeline", "ckpt_integrity",
            "ps_embedding", "ps_fault", "serving_fleet",
            "inference_compiler", "online_learning", "slo_alerting",
            "root_cause")


def _section_bert(on_tpu, recalibrate=False):
    """Headline: ERNIE/BERT-base pretrain step. First child of a run, so it
    is also the one that measures (or --recalibrate re-measures) the chip
    floors every later child reads from the disk cache."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import bert

    calib = _calibration(recalibrate=recalibrate)

    # BERT-base config; bf16 matmuls via default precision on TPU.
    cfg = bert.BertConfig(num_layers=12, hidden_size=768, num_heads=12,
                          ffn_size=3072, vocab_size=30522,
                          hidden_dropout=0.1, attn_dropout=0.1)
    batch, seq = (64, 512) if on_tpu else (2, 128)

    # bf16 AMP (master weights stay f32; no loss scaling needed for bf16) —
    # the production ERNIE recipe; MXU runs bf16, accumulates f32.
    def _opt():
        from paddle_tpu.contrib import mixed_precision as mp
        return mp.decorate(fluid.optimizer.Adam(1e-4), dtype="bfloat16",
                           use_dynamic_loss_scaling=False)

    main_prog, startup, feeds, loss = bert.build_pretrain_program(
        cfg, batch, seq, optimizer_factory=_opt)

    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)

        # int32 ids: JAX x32 mode truncates int64 feeds anyway — avoid the
        # per-step host-side conversion (VERDICT r1 weak #1)
        rng = np.random.RandomState(0)
        feed = {
            "src_ids": rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"),
            "pos_ids": np.tile(np.arange(seq), (batch, 1)).astype("int32"),
            "sent_ids": np.zeros((batch, seq), dtype="int32"),
            "input_mask": np.ones((batch, seq), dtype="float32"),
            "mlm_labels": rng.randint(0, cfg.vocab_size, (batch, seq, 1)).astype("int32"),
        }

        dt = _time_steps(exe, main_prog, feed, loss, 20 if on_tpu else 3)

    tokens_per_sec = batch * seq / dt
    n_params = bert.param_count(cfg)
    flops_per_token = 6 * n_params  # fwd+bwd dense estimate
    mfu = tokens_per_sec * flops_per_token / calib.peak_flops
    return {
        "metric": "ernie_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.35, 4),
        "extra": {"mfu": round(mfu, 4), "batch": batch, "seq_len": seq,
                  "params": n_params, "step_ms": round(dt * 1e3, 2),
                  "device": str(jax.devices()[0]),
                  "calibration": calib.to_dict()},
    }


def _section_resnet50(on_tpu):
    rn_ips, rn_mfu, rn_ms, rn_roofline = bench_resnet(on_tpu)
    return {"extra": {
        "resnet50_imgs_per_sec_per_chip": rn_ips,
        "resnet50_mfu": rn_mfu,
        "resnet50_step_ms": rn_ms,
        "resnet50_vs_baseline": (round(rn_mfu / 0.35, 4)
                                 if rn_mfu is not None else None),
        "resnet50_roofline_frac": (rn_roofline or {}).get("frac"),
        "resnet50_roofline": rn_roofline,
        "resnet50_conv_fusion_speedup": (
            (rn_roofline or {}).get("conv_fusion_speedup")),
    }}


def _section_deepfm(on_tpu):
    rate, ms, dfm_roofline = bench_deepfm(on_tpu)
    return {"extra": {
        "deepfm_rate": rate,
        "deepfm_step_ms": ms,
        "deepfm_vs_baseline": (dfm_roofline or {}).get("frac"),
        "deepfm_roofline": dfm_roofline,
    }}


def _section_nmt_big(on_tpu):
    rate, ms, nmt_mfu, nb, nmt_shapes, sp_speedup = bench_nmt(on_tpu)
    first = nmt_shapes[0] if nmt_shapes else {}
    return {"extra": {
        "nmt_big_rate": rate,            # NON-PAD target tokens/s
        "nmt_big_step_ms": ms,
        "nmt_big_mfu": nmt_mfu,
        "nmt_big_vs_baseline": (round(nmt_mfu / 0.35, 4)
                                if nmt_mfu is not None else None),
        "nmt_big_buckets": nb,
        "nmt_big_shapes": nmt_shapes,   # per-shape fill rate + MFU
        "nmt_big_hbm_plan": first.get("hbm_plan"),
        "nmt_big_roofline_frac": first.get("roofline_frac"),
        "nmt_big_attn": first.get("attn"),
        "nmt_big_sparse_speedup": sp_speedup,
    }}


def _section_ring_attn(on_tpu):
    """Pallas ring attention evidence (VERDICT r3 #5, protocol per r4 #7):
    fwd speedup over the jnp-oracle ring at T=4096 causal on this chip
    (sp=1 ring — the kernel is the variable). INTERLEAVED segments, median
    + IQR per arm, so that drift over the run hits both arms alike."""
    extras = {}
    extras["ring_attn_pallas_speedup_t4k"] = (
        _bench_ring_attn(extras) if on_tpu else None)
    return {"extra": extras}


def _section_dygraph(on_tpu):
    """dygraph PreparedOp jit-cache evidence (VERDICT r3 #9): transformer-
    style MLP train step, cached vs raw per-primitive dispatch."""
    from paddle_tpu import planner

    dy = plan_dict = None
    if on_tpu:
        from paddle_tpu.tools.op_bench import bench_dygraph_mlp
        # batch ladder: the MLP arms are raw arrays, not a Program, so the
        # footprint planner picks the largest batch whose analytic bytes
        # fit the HBM budget
        cands = [(planner.Plan(0, "none", K),
                  _dygraph_footprint_bytes(64 // K))
                 for K in (1, 2, 4)]
        plan = planner.plan_for_footprint(cands, where="bench/dygraph")
        plan_dict = plan.to_dict()
        dy = bench_dygraph_mlp(steps=20,
                               batch=max(1, 64 // plan.microbatch))
    extras = {}
    extras["dygraph_hbm_plan"] = plan_dict
    extras["dygraph_jit_cache_speedup"] = (dy or {}).get("speedup")
    extras["dygraph_step_ms"] = (dy or {}).get("cached_ms")
    if dy:
        extras["dygraph_cached_ms"] = {
            "median": dy.get("cached_ms"), "iqr": dy.get("cached_iqr_ms"),
            "n_segments": dy.get("n_segments")}
        extras["dygraph_uncached_ms"] = {
            "median": dy.get("uncached_ms"),
            "iqr": dy.get("uncached_iqr_ms")}
    return {"extra": extras}


def _section_input_pipeline(on_tpu):
    """Async input pipeline (dataio.DeviceLoader + FetchHandle): sync vs
    prefetch+in-flight steps/s with a slow reader (host cost ~50% of the
    synchronous step); outputs_identical doubles as the handle-path
    bitwise-equivalence check."""
    from paddle_tpu.tools.pipeline_bench import run_pipeline_bench
    return {"extra": {"input_pipeline": run_pipeline_bench()}}


# section name -> fn(on_tpu) returning {"extra": {...}} (bert also returns
# the headline fields). The one-extra sections each own the key named
# after them:
#   dispatch_overhead  host dispatch microbenchmark (ROADMAP item 4: <5% at
#                      batch-1): run vs run_batched vs train_scanned
#   ckpt_integrity     manifest'd blocking save / verify / restore latency +
#                      idle chaos-probe cost (PR 8)
#   ps_embedding       prefetch/async-push overlap A/B over socket shards,
#                      staleness 0/1 exactness, 2x-HBM aggregate table
#   ps_fault           SIGKILL a real pserver mid-run: recovery pause and
#                      bitwise-exact continuation (PR 10)
#   serving_fleet      1-vs-N replica scale-out, zero-downtime swap pause,
#                      PS-backed CTR arm vs a local table (PR 11)
#   inference_compiler per-pass attribution, int8-vs-bf16 served throughput
#                      at gated accuracy, N=3 tenant co-hosting (PR 16)
#   online_learning    train-from-stream + dynamic vocab + delta checkpoints
#                      + delta push to serving (ISSUE 14)
#   slo_alerting       SIGKILL a pserver under a live train+serve stack —
#                      pages fire within two sweeps and resolve (ISSUE 17)
#   root_cause         delay_ms fault at exec.dispatch — the page arrives
#                      annotated with culprit kernels + /history (ISSUE 20)
_SECTION_FNS = {
    "bert": _section_bert,
    "resnet50": _section_resnet50,
    "deepfm": _section_deepfm,
    "dispatch_overhead": lambda on_tpu: {"extra": {
        "dispatch_overhead": bench_dispatch_overhead(on_tpu)}},
    "nmt_big": _section_nmt_big,
    "ring_attn": _section_ring_attn,
    "dygraph": _section_dygraph,
    "input_pipeline": _section_input_pipeline,
    "ckpt_integrity": lambda on_tpu: {"extra": {
        "ckpt_integrity": bench_ckpt_integrity()}},
    "ps_embedding": lambda on_tpu: {"extra": {
        "ps_embedding": bench_ps_embedding(on_tpu)}},
    "ps_fault": lambda on_tpu: {"extra": {
        "ps_fault": bench_ps_fault(on_tpu)}},
    "serving_fleet": lambda on_tpu: {"extra": {
        "serving_fleet": bench_serving_fleet(on_tpu)}},
    "inference_compiler": lambda on_tpu: {"extra": {
        "inference_compiler": bench_inference_compiler(on_tpu)}},
    "online_learning": lambda on_tpu: {"extra": {
        "online_learning": bench_online_learning(on_tpu)}},
    "slo_alerting": lambda on_tpu: {"extra": {
        "slo_alerting": bench_slo_alerting(on_tpu)}},
    "root_cause": lambda on_tpu: {"extra": {
        "root_cause": bench_root_cause(on_tpu)}},
}

# the extras key that carries a failed child's error text, where the
# benchmark's consumers already know one; the rest get {name: {"error"}}
_ERROR_KEYS = {"resnet50": "resnet50_error", "deepfm": "deepfm_error",
               "nmt_big": "nmt_big_error", "ring_attn": "ring_attn_error",
               "dygraph": "dygraph_bench_error"}


def _run_section_child(name, recalibrate=False):
    """`bench.py --section NAME` entry point: run ONE section in this
    process and print its result as a single tagged JSON line."""
    import jax

    from paddle_tpu import planner
    from paddle_tpu.observability.flight import get_flight_recorder

    if name not in _SECTION_FNS:
        raise ValueError(f"unknown bench section {name!r}")
    on_tpu = jax.devices()[0].platform == "tpu"
    try:
        with get_flight_recorder().guard(f"bench/{name}"), \
                planner.guard(f"bench/{name}"):
            if os.environ.get("PDTPU_BENCH_FORCE_OOM") == name:
                # test hook for the isolation contract itself: a synthetic
                # OOM deep in one section must not cascade past it, and must
                # surface as HbmBudgetError carrying the plan in effect
                plan = planner.Plan(0, "none", 1, source="unconstrained",
                                    fits=True)
                planner._record(plan, [plan], f"bench/{name}")
                raise RuntimeError(
                    f"RESOURCE_EXHAUSTED: forced OOM in section {name!r} "
                    f"(PDTPU_BENCH_FORCE_OOM)")
            if name == "bert":
                result = _section_bert(on_tpu, recalibrate=recalibrate)
            else:
                result = _SECTION_FNS[name](on_tpu)
    except planner.HbmBudgetError as e:
        # structured OOM record for the parent: the active plan and the
        # full HbmBudgetError text (which names it) — the parent merges
        # in the flight-dump path. Re-raised so in-process callers (tests)
        # see the exception and the subprocess exits nonzero.
        print("BENCH_SECTION_ERROR " + json.dumps({
            "error": f"HbmBudgetError: {str(e)[:500]}",
            "plan": e.plan.to_dict() if e.plan is not None else None,
        }), flush=True)
        raise
    print("BENCH_SECTION_JSON " + json.dumps(
        {"result": result, "memory": _device_memory_snapshot()}))


def _dygraph_footprint_bytes(batch, width=256, depth=4):
    """Analytic live-bytes estimate for one dygraph MLP train step:
    params + grads + optimizer state f32, plus ~6 activation copies per
    layer (fwd save + bwd) — deliberately conservative."""
    params = (depth + 1) * width * width + 2 * depth * width
    acts = 6 * (depth + 2) * batch * width
    return 4 * (3 * params + acts)


def _run_section_subprocess(name, extras, timeout=2400, recalibrate=False):
    """Run one section via `bench.py --section NAME` in a fresh
    interpreter. Returns (result, error_record): exactly one is None. On
    failure the error record carries the child's last stderr line and
    the path of the flight dump the child wrote (if any)."""
    import glob
    import subprocess
    import tempfile

    env = dict(os.environ)
    flight_dir = env.setdefault("PDTPU_FLIGHT_DIR",
                                tempfile.mkdtemp(prefix="pdtpu_flight_"))
    before = set(glob.glob(os.path.join(flight_dir, "flight_*.json")))
    cmd = [sys.executable, os.path.abspath(__file__), "--section", name]
    if recalibrate:
        cmd.append("--recalibrate")
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              env=env, timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, {"error": f"section timed out after {timeout}s",
                      "flight_dump": None}
    payload = err_payload = None
    for line in (proc.stdout or "").splitlines():
        if line.startswith("BENCH_SECTION_JSON "):
            try:
                payload = json.loads(line[len("BENCH_SECTION_JSON "):])
            except json.JSONDecodeError:
                payload = None
        elif line.startswith("BENCH_SECTION_ERROR "):
            try:
                err_payload = json.loads(line[len("BENCH_SECTION_ERROR "):])
            except json.JSONDecodeError:
                err_payload = None
    if payload is not None:
        extras.setdefault("section_memory", {})[name] = payload.get("memory")
        extras.setdefault("section_peak_bytes", {})[name] = (
            (payload.get("memory") or {}).get("peak_bytes_in_use"))
    if proc.returncode == 0 and payload is not None:
        return payload.get("result"), None
    new_dumps = sorted(
        set(glob.glob(os.path.join(flight_dir, "flight_*.json"))) - before,
        key=os.path.getmtime)
    dump = new_dumps[-1] if new_dumps else None
    if err_payload is not None:
        # structured HbmBudgetError from the child: the record names the
        # plan that was active when HBM ran out, never a bare
        # RESOURCE_EXHAUSTED string
        err_payload["flight_dump"] = dump
        err_payload.setdefault("error", "HbmBudgetError (no detail)")
        return None, err_payload
    tail = [ln for ln in (proc.stderr or "").strip().splitlines() if ln]
    return None, {
        "error": f"exit {proc.returncode}: "
                 f"{tail[-1][:160] if tail else 'no stderr'}",
        "flight_dump": dump}


def _time_steps(exe, prog, feed, loss, iters):
    """Shared measurement protocol: 2 compile/warmup runs, `iters` async
    steps (return_numpy=False so dispatch overlaps device compute), one
    trailing sync; returns seconds/step."""
    exe.run(prog, feed=feed, fetch_list=[loss])
    exe.run(prog, feed=feed, fetch_list=[loss])
    t0 = time.time()
    for _ in range(iters):
        out = exe.run(prog, feed=feed, fetch_list=[loss],
                      return_numpy=False)
    np.asarray(out[0])
    return (time.time() - t0) / iters



def bench_resnet(on_tpu, calib=None):
    """ResNet-50 train-step throughput (BASELINE config 2). Returns
    (imgs_per_sec, mfu, step_ms, roofline dict).

    Round-4 roofline (supersedes round 3, whose host-timed microbench rates
    were too low — see observability/calibrate.py:measure_floors). Wall
    step 59.8→~51 ms at batch 128 this round from host-dispatch fixes
    alone (executor._AutoLayoutStep fast path: per-step signature hashing
    + per-leaf Format construction was ~13 ms/step of unhidden Python).
    Device time (xplane trace, 3-step capture): 46.5 ms across 3644
    kernels — ~31 ms conv+BN-epilogue fusions (XLA fuses the BN stats
    reductions AND the parameter updates into the conv backward kernels;
    the round-3 '11 ms of update kernels' were really wgrad reductions
    reading [B,C,H,W] activations at ~430 GB/s), 1.7 ms copies, 0.7 ms
    maxpool backward. XLA stages activations up to 102 MB through VMEM
    (S(1) buffers in the scheduled HLO), so hand pass-count models
    overestimate HBM traffic; the floors below are measured instead.
    Levers tried and REJECTED by measurement this round: selective remat
    of bn/relu/add (PDTPU_REMAT_OPS path: 62.0 ms vs 50.6 — recompute
    adds passes, removes none), batch 256 (105.2 ms, throughput-neutral:
    bandwidth-bound), scoped-vmem 64 MiB flag (54.5 ms), bf16 BN apply
    (y = a·x+b computed in bf16 with f32 stats: 51.8 ms — the f32
    normalize math was already fused for free), horizontal update fusion
    (round 3: slower, and the trace shows updates already ride the wgrad
    fusions). Round-3 rejections that still stand: Pallas standalone
    fused BN (116 ms, layout fight), MXU-contraction stats. The reported
    frac compares the step against an AGGRESSIVE floor (conv MXU time +
    6 activation passes, i.e. near-perfect VMEM forwarding); the
    structural 13-pass floor exceeds the measured step — XLA's VMEM
    staging already beats kernel-by-kernel scheduling — so the honest
    statement is: the step sits between the two bounds, every
    single-lever change measured regresses it, and the 0.35-MFU bar
    remains out of reach for BN-heavy convnets on this chip while
    matmul-bound workloads clear it (BERT 0.41).

    Round 5 (VERDICT r4 #2): the two untried levers, measured —
    space-to-depth stem ADOPTED (models/resnet.py _s2d_stem: the MLPerf
    2x2-block trick; stem fwd+bwd 1.35 -> 1.05 ms at batch 128); an
    NHWC-native conv measured EXACTLY neutral (2.462 vs 2.464 ms fwd+bwd
    for the 3x3/256ch mid-network conv — XLA TPU normalizes conv layouts
    internally, so logical NCHW costs nothing). And the per-kernel
    accounting the verdict asked for: `per_kernel` in the roofline dict
    lists every kernel >=0.5 ms/step from a live 2-step trace with its
    achieved GB/s and TFLOP/s and utilization vs the measured chip
    bounds, plus the tail aggregate — the 'missing' device time is
    thousands of sub-10us kernels, not slow big ones: the >=1 ms kernels
    all run AT or ABOVE the measured stream bound (their bytes include
    VMEM-staged re-reads, hence >1.0 utilizations)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    batch, hw, classes = (128, 224, 1000) if on_tpu else (2, 32, 10)

    def _build(fusion_mode):
        """Build the train program with conv+BN fusion on/off. resnet.py
        reads PDTPU_CONV_BN_FUSION at graph-build time, so the env must
        bracket the build, not just the run."""
        prev = os.environ.get("PDTPU_CONV_BN_FUSION")
        if fusion_mode is None:
            os.environ.pop("PDTPU_CONV_BN_FUSION", None)
        else:
            os.environ["PDTPU_CONV_BN_FUSION"] = fusion_mode
        try:
            main_prog = fluid.Program()
            startup = fluid.Program()
            with fluid.program_guard(main_prog, startup):
                img = fluid.layers.data("img", [3, hw, hw])
                label = fluid.layers.data("label", [1], dtype="int64")
                logits = resnet.resnet(img, 50, classes, stem_s2d=on_tpu)
                loss = fluid.layers.mean(
                    fluid.layers.softmax_with_cross_entropy(logits, label))
                from paddle_tpu.contrib import mixed_precision as mp
                opt = mp.decorate(fluid.optimizer.Momentum(0.1, 0.9),
                                  dtype="bfloat16",
                                  use_dynamic_loss_scaling=False)
                opt.minimize(loss)
            return main_prog, startup, loss
        finally:
            if prev is None:
                os.environ.pop("PDTPU_CONV_BN_FUSION", None)
            else:
                os.environ["PDTPU_CONV_BN_FUSION"] = prev

    # kernel-campaign headline arm: Pallas conv+BN epilogue fusion on TPU,
    # the bitwise XLA composition of the same fused op on CPU. The env
    # override lets a run force either arm for triage.
    fusion_mode = os.environ.get("PDTPU_CONV_BN_FUSION",
                                 "pallas" if on_tpu else "xla")
    main_prog, startup, loss = _build(fusion_mode)
    unfused = _build(None)

    exe = fluid.Executor(fluid.TPUPlace())
    # own scope: params/optimizer state free when the bench returns —
    # otherwise earlier models' live HBM pushes later benches into XLA
    # rematerialization (measured: NMT MFU 0.324 alone vs 0.079 after
    # BERT+ResNet buffers were left resident)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        rng = np.random.RandomState(0)
        # stage the batch on device once (a production input pipeline keeps
        # batches prefetched in HBM; the 77 MB host→device transfer per step
        # would otherwise dominate the measurement)
        import jax.numpy as jnp
        feed = {
            "img": jnp.asarray(rng.randn(batch, 3, hw, hw).astype("float32")),
            "label": jnp.asarray(
                rng.randint(0, classes, (batch, 1)).astype("int32")),
        }
        dt = _time_steps(exe, main_prog, feed, loss, 20 if on_tpu else 2)
        calib = calib or _calibration()
        floors = calib.floors
        per_kernel = None
        if on_tpu:
            try:
                from paddle_tpu.tools.roofline import capture_kernel_table
                per_kernel = capture_kernel_table(
                    lambda: exe.run(main_prog, feed=feed,
                                    fetch_list=[loss]), floors)
            except Exception as e:  # trace plumbing must not kill the bench
                per_kernel = {"error": str(e)[:120]}
    # A/B arm: same graph without the fused conv+BN op (seed lowering).
    # Fresh scope so the arms don't share optimizer state.
    with fluid.scope_guard(fluid.Scope()):
        exe.run(unfused[1])
        dt_unfused = _time_steps(exe, unfused[0], feed, unfused[2],
                                 20 if on_tpu else 2)
    fusion_speedup = round(dt_unfused / dt, 4) if dt > 0 else None
    imgs_per_sec = batch / dt
    # ResNet-50 @224²: ~4.1 GFLOP fwd; fwd+bwd ≈ 3×
    flops_per_img = 3 * 4.1e9 if hw == 224 else 3 * 4.1e9 * (hw / 224) ** 2

    # self-measured no-overlap floor (see docstring): conv FLOPs at the
    # chip's measured chained-matmul rate, plus SIX mandatory activation
    # passes over the ΣS=2.71 GB (batch 128, bf16) of conv/BN outputs at
    # the measured stream rate — fwd: write conv out, read it for the
    # one-pass stats, write the normalized output; bwd: read the incoming
    # grad, read the saved conv out (BN grad reductions + dx), write dx.
    # VMEM forwarding (XLA stages buffers up to 102 MB in S(1) space) can
    # beat individual passes, which is why the achieved step can sit
    # close to or above this floor.
    mm_tflops, stream_gbs = floors
    conv_floor_ms = batch * flops_per_img / (mm_tflops * 1e12) * 1e3
    scale = (batch / 128) * (hw / 224) ** 2
    # two bounds on the activation-pass traffic (ΣS = 2.71 GB of bf16
    # conv/BN outputs at batch 128): the STRUCTURAL 13-pass count every
    # kernel-by-kernel schedule needs (fwd conv W, stats R, norm R+W; bwd
    # grad-reduction R dy + R x, dx R dy + R x + W, dgrad R+W, wgrad 2R)
    # and an AGGRESSIVE 6-pass bound assuming near-perfect VMEM
    # forwarding. The measured step lands between them: XLA's S(1) VMEM
    # staging already removes ~3 passes' worth vs the structural count.
    floor6_ms = conv_floor_ms + 6 * 2.71 * scale / stream_gbs * 1e3
    floor13_ms = conv_floor_ms + 13 * 2.71 * scale / stream_gbs * 1e3
    # shared attribution (observability/perf.py): MFU and the max(mm,
    # stream) roofline fraction from the same code every compiled program
    # reports through the perf/* gauges. The 6-pass frac above stays the
    # headline — it models the SUM of non-overlapping conv + activation
    # passes, a tighter convnet-specific bound than attribute()'s max.
    from paddle_tpu.observability import perf
    att = perf.attribute(flops=batch * flops_per_img,
                         bytes_accessed=6 * 2.71e9 * scale,
                         seconds=dt, calib=calib)
    mfu = att["mfu"]
    roofline = {
        "matmul_tflops_meas": round(mm_tflops, 1),
        "stream_gbs_meas": round(stream_gbs, 1),
        "calibration_source": calib.source,
        "conv_floor_ms": round(conv_floor_ms, 2),
        "floor6_ms": round(floor6_ms, 2),
        "floor13_ms": round(floor13_ms, 2),
        "frac": round(min(1.0, floor6_ms / (dt * 1e3)), 4),
        "frac_vs_structural_13pass": round(
            min(1.0, floor13_ms / (dt * 1e3)), 4),
        "attribution": {k: (round(v, 4) if isinstance(v, float) else v)
                        for k, v in att.items()},
        "per_kernel": per_kernel,
        "conv_fusion_mode": fusion_mode,
        "conv_fusion_speedup": fusion_speedup,
        "step_ms_unfused": round(dt_unfused * 1e3, 2),
    }
    return (round(imgs_per_sec, 2), round(mfu, 4), round(dt * 1e3, 2),
            roofline)



def bench_deepfm(on_tpu, calib=None):
    """DeepFM CTR train-step (BASELINE config 5), round 5: CRITEO-scale
    33.5M-row table with the tables on EXACT Adagrad (VERDICT r4 #1 —
    "a real optimizer, not SGD-by-necessity") via the packed row-major
    table path (ops/deferred_rows.py): the [V, 17] embedding+w1 columns
    and the [V, 17] Adagrad accumulator ride in ONE [V, 128] uint16 row
    (bit-split f32 — the Downpour g2sum in-row layout), so each step is
    one lane-aligned row gather + one row scatter-set of the touched rows.
    Measured v5e costs that drove the design: XLA scatter into the
    column-major f32 table costs ~6.4 ns per touched ELEMENT (so the
    r4 'O(table) pass' model was really a per-element tax, and Adagrad
    would pay it twice); the packed row-major layout does the same
    update at ~70 ns per touched ROW.

    Metric (same shape as r4): achieved effective HBM rate over the
    self-measured stream rate, where modeled bytes = what the NAIVE XLA
    lowering of this exact config (dense adagrad kernels on f32 tables)
    must move per step — one read+write of the param table AND the
    accumulator table. The packed path moves far less (actual_gb
    reported alongside); frac > 1 (capped) means the step beats the
    naive streaming bound outright. A direct A/B against the measured
    naive path is reported in the roofline dict.

    Returns (exs/s, ms, roofline dict)."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import deepfm

    batch, vocab = (4096, 33_554_432) if on_tpu else (64, 10_000)

    def build(**kw):
        return deepfm.build_train_program(
            vocab_size=vocab, is_sparse=True, fused_table=True,
            embedding_optimizer="adagrad", **kw)

    rng = np.random.RandomState(0)
    feed = {
        "sparse_ids": jnp.asarray(
            rng.randint(0, vocab, (batch, 26)).astype("int32")),
        "dense": jnp.asarray(rng.rand(batch, 13).astype("float32")),
        "label": jnp.asarray(
            rng.randint(0, 2, (batch, 1)).astype("float32")),
    }

    main_p, startup, feeds, loss, _ = build(
        packed_rows={"rows_per_step": batch * 26})
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        dt = _time_steps(exe, main_p, feed, loss, 48 if on_tpu else 2)

    # scan-driver path: the same program driven by Executor.train_scanned
    # — K-step on-device lax.scan dispatches fed from the DeviceLoader
    # prefetch queue. This is the configuration the 400k ex/s target is
    # scored on.
    scan_k = 16
    n_scan = scan_k * (6 if on_tpu else 2)
    dt_scan, scan_err = None, None
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main_p, feed=feed, fetch_list=[loss])
            # first pass compiles the scan; second is the measurement
            exe.train_scanned(main_p, reader=lambda: iter([feed] * n_scan),
                              scan_steps=scan_k, fetch_list=[loss])
            t0 = time.time()
            exe.train_scanned(main_p, reader=lambda: iter([feed] * n_scan),
                              scan_steps=scan_k, fetch_list=[loss])
            dt_scan = (time.time() - t0) / n_scan
    except Exception as e:
        scan_err = str(e)[:160]

    # hot-cache arm (ISSUE 12): the same deepfm step driven through the
    # PS tier on a ZIPFIAN id stream — streaming (hot_rows=0: every
    # touched row pulled+pushed per step) vs the device-resident hot
    # slab (LFU-admitted rows never leave HBM). In-process shards on
    # purpose: this arm isolates the host<->HBM row traffic the cache
    # removes; socket latency is bench_ps_embedding's subject.
    hot_cache = {"error": None}
    dt_hot = None
    try:
        from paddle_tpu.ps import (PsEmbeddingTier, PsTableBinding,
                                   RangeSpec, ShardedTable)
        cap = batch * 26
        hot_rows = (1 << 18) if on_tpu else 4096
        n_hot = 24 if on_tpu else 20
        zrng = np.random.RandomState(11)
        zfeeds = [{"sparse_ids": ((zrng.zipf(1.5, (batch, 26)) - 1)
                                  % vocab).astype("int64"),
                   "dense": zrng.rand(batch, 13).astype("float32"),
                   "label": zrng.randint(0, 2,
                                         (batch, 1)).astype("float32")}
                  for _ in range(n_hot)]

        def _ps_arm(hr, warmup=4):
            table = ShardedTable.build_in_process(
                "fm_t", RangeSpec.even(vocab, 4))
            main_h, startup_h, _, loss_h, _ = deepfm.build_train_program(
                vocab_size=hr + cap if hr else cap, is_sparse=True,
                fused_table=True, embedding_optimizer="adagrad",
                packed_rows={"rows_per_step": cap})
            losses, dt_h, st_warm = [], None, None
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup_h)
                tier = PsEmbeddingTier(
                    main_h, [PsTableBinding("fm_t", table, ["sparse_ids"])],
                    pull_ahead=2, push_depth=1, hot_rows=hr)
                try:
                    t0, n_timed = None, 0
                    for i, prep in enumerate(tier.steps(
                            lambda: iter(zfeeds))):
                        (lv,) = tier.run_step(exe, prep,
                                              fetch_list=[loss_h])
                        losses.append(float(np.asarray(lv)))
                        if i + 1 == warmup:
                            t0 = time.time()
                            st_warm = tier.stats()["fm_t"].get("hot_cache")
                        elif i + 1 > warmup:
                            n_timed += 1
                    tier.flush()
                    dt_h = ((time.time() - t0) / n_timed
                            if t0 is not None and n_timed else None)
                    st = tier.stats()["fm_t"].get("hot_cache")
                finally:
                    tier.close()
            # steady-state lookup hit rate over the SAME window the
            # ex/s is measured on (post-warmup delta): the cumulative
            # number drags the unavoidable cold start + the two-touch
            # admission ramp into an otherwise-steady measurement
            if st is not None and st_warm is not None:
                dh = st["lookup_hits"] - st_warm["lookup_hits"]
                dm = st["lookup_misses"] - st_warm["lookup_misses"]
                st = dict(st, steady_lookup_hit_rate=(
                    dh / (dh + dm) if dh + dm else None))
            return dt_h, losses, st

        dt_stream, losses_stream, _ = _ps_arm(0)
        dt_hot, losses_hot, cache_st = _ps_arm(hot_rows)
        hot_cache = {
            "hot_rows": hot_rows,
            "zipf_a": 1.5,
            # fraction of embedding LOOKUPS served from resident HBM
            # rows, occurrence-weighted, over the same post-warmup
            # window the ex/s is measured on — the acceptance number;
            # cold_hit_rate keeps the from-step-0 cumulative view, and
            # row_hit_rate is the unique-rows-per-step view that maps
            # 1:1 to pull/push traffic saved
            "hit_rate": (round(cache_st["steady_lookup_hit_rate"], 4)
                         if cache_st and cache_st.get(
                             "steady_lookup_hit_rate") is not None
                         else None),
            "cold_hit_rate": (round(cache_st["lookup_hit_rate"], 4)
                              if cache_st and cache_st["lookup_hit_rate"]
                              is not None else None),
            "row_hit_rate": (round(cache_st["hit_rate"], 4)
                             if cache_st and cache_st["hit_rate"]
                             is not None else None),
            "evictions": cache_st["evictions"] if cache_st else None,
            "writeback_bytes": (cache_st["writeback_bytes"]
                                if cache_st else None),
            "rate": round(batch / dt_hot, 1) if dt_hot else None,
            "streaming_rate": (round(batch / dt_stream, 1)
                               if dt_stream else None),
            "speedup_vs_streaming": (round(dt_stream / dt_hot, 2)
                                     if dt_stream and dt_hot else None),
            # same Zipfian feeds, staleness-0-exact machinery on both
            # arms: measured, not assumed
            "bitwise_equal": losses_stream == losses_hot,
        }
    except Exception as e:
        hot_cache = {"error": str(e)[:160]}
    dt_hot_arm = (dt_hot if hot_cache.get("error") is None and dt_hot
                  else None)

    # the naive-lowering A/B on the same chip: dense adagrad kernels,
    # f32 tables, XLA scatter applies (what a literal translation pays)
    naive_ms = None
    if on_tpu:
        try:
            main_n, startup_n, _, loss_n, _ = build()
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup_n)
                naive_ms = round(
                    _time_steps(exe, main_n, feed, loss_n, 12) * 1e3, 2)
        except Exception:
            naive_ms = None

    # modeled mandatory traffic of the naive lowering: param + accumulator
    # table passes (r4 modeled the param pass only — SGD config) + gathers
    table_bytes = 2 * 2 * (vocab * 17 * 4)
    gather_bytes = 2 * batch * 26 * 17 * 4
    bytes_total = table_bytes + gather_bytes
    # actual traffic of the packed path: one [128]-lane u16 row gather +
    # one row scatter-set per touched row + dense net (noise)
    actual_bytes = 2 * batch * 26 * 128 * 2 + gather_bytes
    # headline rate is the best path (scan driver — or the hot-cache PS
    # arm — when it wins); the per-step dispatch time stays visible
    best = min(d for d in (dt, dt_scan, dt_hot_arm) if d is not None)
    calib = calib or _calibration()
    mm_tflops, stream_gbs = calib.floors
    # shared attribution: with flops≈0 the roofline fraction IS
    # achieved_gbs/stream_gbs — same number the old hand math produced,
    # now from the code path every compiled program reports through
    from paddle_tpu.observability import perf
    att = perf.attribute(bytes_accessed=bytes_total, seconds=best,
                         calib=calib)
    achieved_gbs = att["achieved_gbs"]
    roofline = {
        "vocab": vocab,
        "optimizer": "adagrad (exact, packed row-major state-in-row)",
        "modeled_naive_gb_per_step": round(bytes_total / 1e9, 3),
        "actual_gb_per_step": round(actual_bytes / 1e9, 3),
        "effective_gbs": round(achieved_gbs, 1),
        "stream_gbs_meas": round(stream_gbs, 1),
        "calibration_source": calib.source,
        "naive_adagrad_step_ms": naive_ms,
        "speedup_vs_naive": (round(naive_ms / (best * 1e3), 2)
                             if naive_ms else None),
        "frac": round(min(1.0, att["roofline_fraction"]), 4),
        "per_step_dispatch_ms": round(dt * 1e3, 2),
        "scan_step_ms": round(dt_scan * 1e3, 2) if dt_scan else None,
        "scan_k": scan_k,
        # BENCH_r05 chased the 0.957x deepfm_vs_baseline down to the
        # per-step dispatch path being recorded as the headline while the
        # scan driver was faster: record BOTH rates explicitly so the
        # comparator always sees which one the headline ex/s came from
        "per_step_rate": round(batch / dt, 1),
        "scan_rate": round(batch / dt_scan, 1) if dt_scan else None,
        "headline_path": ("hot_cache" if dt_hot_arm and dt_hot_arm == best
                          else "scan" if dt_scan and dt_scan < dt
                          else "per_step"),
        # ISSUE 12: Zipfian-stream A/B of the device-resident hot-row
        # cache against the streaming PS path (hit rate + speedup)
        "hot_cache": hot_cache,
        # the StepProfiler sampling cadence active INSIDE this loop (the
        # PR 6 fix: unsampled steps skip the block_until_ready tax)
        "step_sample_every": int(os.environ.get(
            "PDTPU_STEP_SAMPLE_EVERY", "16")),
    }
    if scan_err:
        roofline["scan_error"] = scan_err
    return round(batch / best, 1), round(best * 1e3, 2), roofline


def bench_ps_embedding(on_tpu):
    """Sharded PS embedding tier (paddle_tpu.ps) on a lookup-bound DeepFM:
    single-host multi-shard, three arms — prefetch off (inline pulls),
    prefetch on (pull_ahead=2, staleness 0), and bounded-async push
    (staleness 1). The overlap claim under test: with the pull prefetcher
    riding the DeviceLoader worker and pushes draining behind compute,
    the step stops paying host pull/push latency, so prefetch-on ex/s
    should clear 1.3x prefetch-off when lookups dominate (tiny dense
    net). Staleness-0 arms must stay bitwise-identical — the tier's remap
    is order-isomorphic and push 0 is synchronous — and the depth-1 arm
    is also exact single-worker via read-your-writes patching; both
    equalities are recorded, not assumed. A fourth arm turns on the
    device-resident hot-row cache (ISSUE 12) on the same feeds —
    recorded for hit rate and, above all, bitwise equality with the
    uncached arms. A final arm trains an aggregate table 2x the
    single-host packed bench size across shards (host DRAM, not HBM, is
    the bound — the point of the tier)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import deepfm
    from paddle_tpu.observability.registry import get_registry
    from paddle_tpu.ps import (PsEmbeddingTier, PsTableBinding, RangeSpec,
                               ShardServer, ShardedTable, SocketClient,
                               make_shards)

    batch, vocab, n_shards, steps = ((4096, 2_097_152, 8, 36) if on_tpu
                                     else (256, 50_000, 4, 16))
    # simulated cross-host RTT on the loopback servers: on a CPU-only
    # host the trainer's "compute" runs on the same cores as the shard
    # serialization, so overlap can only hide WAIT, not work — without a
    # latency term the A/B measures core contention, not overlap. 15 ms
    # models a sub-MB per-shard pull on a ~GbE-class link plus pserver
    # queueing. On TPU
    # the compute is off-host, so the real serialization overlaps → 0.
    sim_net_ms = float(os.environ.get("PDTPU_PS_BENCH_NET_MS",
                                      "0" if on_tpu else "15"))
    fields, cap = 26, batch * 26
    rng = np.random.RandomState(3)
    feeds = [{"sparse_ids": rng.randint(
                  0, vocab, (batch, fields)).astype("int64"),
              "dense": rng.rand(batch, 13).astype("float32"),
              "label": rng.randint(0, 2, (batch, 1)).astype("float32")}
             for _ in range(steps)]
    reg = get_registry()

    def run_arm(pull_ahead, push_depth, arm_vocab=vocab, arm_feeds=feeds,
                warmup=3, hot_rows=0, scrape_hz=0.0):
        hit0 = reg.counter("ps/prefetch_hit").value
        miss0 = reg.counter("ps/prefetch_miss").value
        # socket transport on purpose: pull/push cost (serialize + TCP +
        # shard gather) is what the prefetcher/pusher overlap against —
        # in-process shards make both arms lookup-free and the A/B moot
        spec = RangeSpec.even(arm_vocab, n_shards)
        servers = [ShardServer([sh], delay_ms=sim_net_ms).serve_in_thread()
                   for sh in make_shards("fm_t", spec)]
        table = ShardedTable(
            "fm_t", spec, [SocketClient(s.endpoint) for s in servers],
            push_clients=[SocketClient(s.endpoint) for s in servers])
        # ISSUE 13's off-the-hot-path claim: federation rides a daemon
        # thread plus the shards' `metrics` op, never the step itself —
        # scrape the trainer registry AND every shard socket at
        # `scrape_hz` while this arm trains, then A/B step time
        scraper, fed_doc = None, None
        if scrape_hz:
            from paddle_tpu.observability.federate import (FederatedScraper,
                                                           ScrapeTarget)
            scraper = FederatedScraper(
                [ScrapeTarget.local(name="trainer", role="trainer")]
                + [ScrapeTarget.ps(s.endpoint, shard=i)
                   for i, s in enumerate(servers)],
                interval_s=1.0 / scrape_hz).start()
        # hot_rows > 0 grows the cache param into the persistent slab
        # ([hot_rows + per-step rows]) the HotRowCache manages
        main, startup, _, loss, _ = deepfm.build_train_program(
            vocab_size=cap + hot_rows, lr=0.05, is_sparse=True,
            fused_table=True, embedding_optimizer="adagrad",
            packed_rows={"rows_per_step": cap}, hidden_sizes=(64,))
        exe = fluid.Executor(fluid.TPUPlace())
        losses, dt = [], None
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            tier = PsEmbeddingTier(
                main, [PsTableBinding("fm_t", table, ["sparse_ids"])],
                pull_ahead=pull_ahead, push_depth=push_depth,
                hot_rows=hot_rows)
            try:
                t0, n_timed = None, 0
                for i, prep in enumerate(tier.steps(
                        lambda: iter(arm_feeds))):
                    (lv,) = tier.run_step(exe, prep, fetch_list=[loss])
                    losses.append(float(np.asarray(lv)))
                    if i + 1 == warmup:
                        t0 = time.time()
                    elif i + 1 > warmup:
                        n_timed += 1
                tier.flush()
                dt = ((time.time() - t0) / n_timed
                      if t0 is not None and n_timed else None)
                stats = tier.stats()["fm_t"]
            finally:
                if scraper is not None:
                    # grab the last background sweep (or force one)
                    # while the shard sockets are still up
                    fed_doc = scraper.last() or scraper.scrape_once()
                    scraper.stop()
                tier.close()
                for s in servers:
                    s.stop()
        res = {
            "rate": round(batch / dt, 1) if dt else None,
            "step_ms": round(dt * 1e3, 2) if dt else None,
            "losses": losses,
            "prefetch_hits": reg.counter("ps/prefetch_hit").value - hit0,
            "prefetch_misses": (reg.counter("ps/prefetch_miss").value
                                - miss0),
            "per_shard_bytes": [
                {"shard": s["shard"], "rows": s["rows"],
                 "pulled": s["bytes_pulled"], "pushed": s["bytes_pushed"]}
                for s in stats["shards"]],
            "hot_cache": stats.get("hot_cache"),
        }
        if fed_doc is not None:
            res["federated"] = fed_doc
        return res

    off = run_arm(0, 0)            # inline pulls, synchronous push
    on0 = run_arm(2, 0)            # prefetch on, staleness 0
    on1 = run_arm(2, 1)            # prefetch + async push (full overlap)
    hot = run_arm(2, 1, hot_rows=2 * cap)  # + device-resident hot rows
    speedup = (round(on1["rate"] / off["rate"], 3)
               if off["rate"] and on1["rate"] else None)
    speedup_s0 = (round(on0["rate"] / off["rate"], 3)
                  if off["rate"] and on0["rate"] else None)

    # ISSUE 13: the same full-overlap arm with a 1 Hz FederatedScraper
    # polling trainer + shards in the background — federation must be
    # provably off the hot path (<1% step-time delta). Clear the tracer
    # first so the trace sidecar covers exactly this arm.
    from paddle_tpu.observability.tracer import get_tracer
    from paddle_tpu.tools.timeline import merge_fleet_traces
    get_tracer().clear()
    obs = run_arm(2, 1, scrape_hz=1.0)
    fed_doc = obs.pop("federated", None)
    scrape_overhead = (round(obs["step_ms"] / on1["step_ms"] - 1.0, 4)
                       if obs["step_ms"] and on1["step_ms"] else None)
    merged_trace = merge_fleet_traces([get_tracer().export_chrome_trace()],
                                      ["trainer"])
    federation = {
        "scrape_hz": 1.0,
        "step_ms_unscraped": on1["step_ms"],
        "step_ms_scraped": obs["step_ms"],
        "step_time_delta_frac": scrape_overhead,
        "off_hot_path": (scrape_overhead is not None
                         and scrape_overhead < 0.01),
        "targets_ok": (fed_doc or {}).get("ok"),
        "signals": (fed_doc or {}).get("signals"),
        "trace_sidecar": _telemetry_out("ps_embedding", "trace",
                                        merged_trace),
        "metrics_sidecar": _telemetry_out("ps_embedding", "metrics",
                                          fed_doc),
    }

    # aggregate table 2x the single-host packed bench size, across shards
    big_vocab = 2 * (33_554_432 if on_tpu else 10_000)
    big = {"vocab": big_vocab, "num_shards": n_shards,
           "aggregate_gb": round(big_vocab * 128 * 2 / 1e9, 2),
           "vs_single_host_packed": 2.0}
    try:
        big_rng = np.random.RandomState(5)
        big_feeds = [{"sparse_ids": big_rng.randint(
                          0, big_vocab, (batch, fields)).astype("int64"),
                      "dense": big_rng.rand(batch, 13).astype("float32"),
                      "label": big_rng.randint(
                          0, 2, (batch, 1)).astype("float32")}
                     for _ in range(6)]
        res = run_arm(2, 1, arm_vocab=big_vocab, arm_feeds=big_feeds,
                      warmup=2)
        big["trained_green"] = bool(np.isfinite(res["losses"]).all())
        big["rate"] = res["rate"]
    except Exception as e:  # RESOURCE_EXHAUSTED here fails the claim
        big["trained_green"] = False
        big["error"] = str(e)[:160]

    # PS-tier roofline (shared calibration + attribution): the host
    # pull/push row traffic the full-overlap arm moves per step, rated
    # against the chip's stream floor. The overlap claim in hardware
    # terms: frac << 1 says the step is NOT bound by moving rows — the
    # prefetcher/pusher hide the traffic — while frac near 1 would mean
    # the tier is saturating the only bound that could justify its cost.
    ps_roofline = None
    if on1["step_ms"]:
        from paddle_tpu.observability import perf
        calib = _calibration()
        moved = sum(s["pulled"] + s["pushed"]
                    for s in on1["per_shard_bytes"])
        per_step = moved / max(len(feeds), 1)
        att = perf.attribute(bytes_accessed=per_step,
                             seconds=on1["step_ms"] / 1e3, calib=calib)
        ps_roofline = {
            "host_bytes_per_step": int(per_step),
            "achieved_gbs": round(att["achieved_gbs"], 3),
            "stream_gbs_meas": round(calib.stream_gbs, 1),
            "calibration_source": calib.source,
            "frac": round(att["roofline_fraction"], 4),
        }

    out = {
        "batch": batch, "vocab": vocab, "num_shards": n_shards,
        "cache_rows": cap,
        "prefetch_off": {k: v for k, v in off.items() if k != "losses"},
        "prefetch_on": {k: v for k, v in on0.items() if k != "losses"},
        "push_depth1": {k: v for k, v in on1.items() if k != "losses"},
        "hot_cache_arm": {k: v for k, v in hot.items() if k != "losses"},
        "transport": "socket",
        "sim_net_ms": sim_net_ms,
        "prefetch_speedup": speedup,
        "prefetch_speedup_staleness0": speedup_s0,
        # both staleness-0 arms run identical f32 math on identical ids;
        # depth-1 exactness is the read-your-writes patching at work
        "staleness0_bitwise_equal": off["losses"] == on0["losses"],
        "push_depth1_bitwise_equal": off["losses"] == on1["losses"],
        # the headline contract of ISSUE 12, measured at bench scale:
        # the hot slab changes WHERE rows live, never what they compute
        "hot_cache_bitwise_equal": off["losses"] == hot["losses"],
        "cache_hit_rate": ((hot["hot_cache"] or {}).get("lookup_hit_rate")
                           if hot["hot_cache"] else None),
        "patched_rows": reg.counter("ps/patched_rows").value,
        "repulls": reg.counter("ps/repulls").value,
        "pull_ms_p50": reg.histogram("ps/pull_ms").percentile(50),
        "push_ms_p50": reg.histogram("ps/push_ms").percentile(50),
        # ISSUE 13: 1 Hz federation A/B + trace/metrics sidecars
        "federation": federation,
        "roofline": ps_roofline,
        "big_table": big,
    }
    return out


def bench_ps_fault(on_tpu):
    """Fault-tolerance tax on the PS tier (PR 10): SIGKILL one real
    pserver subprocess mid-run and measure what recover-and-resume
    costs — the wall-clock pause the worker eats (shard ping-wait +
    verified-checkpoint slice load + push-journal replay) against the
    median healthy step. Exactness is measured, not assumed: the
    interrupted run's losses must bitwise-match the uninterrupted
    baseline (the ISSUE-10 acceptance cell, at bench scale)."""
    import shutil
    import subprocess
    import sys
    import tempfile
    import threading

    import paddle_tpu as fluid
    from paddle_tpu.models import deepfm
    from paddle_tpu.observability.registry import get_registry
    from paddle_tpu.parallel import Checkpointer
    from paddle_tpu.ps import (PsEmbeddingTier, PsTableBinding, RangeSpec,
                               ShardedTable, SocketClient)

    batch, vocab, steps, kill_step = ((1024, 262_144, 18, 8) if on_tpu
                                      else (128, 20_000, 12, 5))
    fields, cap = 26, batch * 26
    sim_net_ms = float(os.environ.get("PDTPU_PS_BENCH_NET_MS",
                                      "0" if on_tpu else "5"))
    rng = np.random.RandomState(7)
    feeds = [{"sparse_ids": rng.randint(
                  0, vocab, (batch, fields)).astype("int64"),
              "dense": rng.rand(batch, 13).astype("float32"),
              "label": rng.randint(0, 2, (batch, 1)).astype("float32")}
             for _ in range(steps)]
    reg = get_registry()
    runner = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "ps_server_runner.py")
    spec = RangeSpec.even(vocab, 2)

    def launch(i, port=0):
        lo, hi = spec.bounds(i)
        p = subprocess.Popen(
            [sys.executable, runner, "--port", str(port),
             "--table", f"fm_t:{lo}:{hi}", "--delay-ms", str(sim_net_ms)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        ep = p.stdout.readline().strip()
        if not ep:
            raise RuntimeError("pserver runner died at boot")
        return p, ep

    # loopback recovers fast; don't let the ping-wait default (100 ms
    # poll) and the stock backoff dominate a millisecond-scale bench
    knobs = {"PDTPU_PS_RETRIES": "60", "PDTPU_PS_RETRY_BACKOFF_MS": "20",
             "PDTPU_PS_TIMEOUT": "10"}
    saved_env = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    ckdir = tempfile.mkdtemp(prefix="pdtpu_bench_psfault_")

    def run(kill):
        procs, eps = [], []
        for i in range(2):
            p, ep = launch(i)
            procs.append(p)
            eps.append(ep)
        table = ShardedTable("fm_t", spec,
                             [SocketClient(ep) for ep in eps])
        restarter = None
        try:
            main, startup, _, loss, _ = deepfm.build_train_program(
                vocab_size=cap, lr=0.05, is_sparse=True, fused_table=True,
                embedding_optimizer="adagrad",
                packed_rows={"rows_per_step": cap}, hidden_sizes=(64,))
            exe = fluid.Executor(fluid.TPUPlace())
            losses, step_ms = [], []
            sc = fluid.Scope()
            with fluid.scope_guard(sc):
                exe.run(startup)
                sub = os.path.join(ckdir, "kill" if kill else "base")
                ck = Checkpointer(sub)
                ck.save(0, program=main, scope=sc,
                        blocking=True, ps_tables={"fm_t": table})
                tier = PsEmbeddingTier(
                    main, [PsTableBinding("fm_t", table, ["sparse_ids"])],
                    pull_ahead=1, push_depth=0)
                tier.attach_checkpointer(ck)
                try:
                    for i, prep in enumerate(tier.steps(
                            lambda: iter(feeds))):
                        if kill and i == kill_step:
                            procs[1].kill()
                            procs[1].wait()
                            port1 = int(eps[1].rsplit(":", 1)[1])

                            def _restart():
                                time.sleep(0.25)
                                procs[1], _ = launch(1, port=port1)

                            restarter = threading.Thread(target=_restart,
                                                         daemon=True)
                            restarter.start()
                        t0 = time.time()
                        (lv,) = tier.run_step(exe, prep, fetch_list=[loss])
                        step_ms.append((time.time() - t0) * 1e3)
                        losses.append(float(np.asarray(lv)))
                    tier.flush()
                finally:
                    tier.close()
            return losses, step_ms
        finally:
            if restarter is not None:
                restarter.join(timeout=10.0)
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()

    try:
        base_losses, base_ms = run(kill=False)
        recov0 = reg.counter("ps/recoveries").value
        retry0 = reg.counter("ps/rpc_retries").value
        kill_losses, kill_ms = run(kill=True)
        healthy = sorted(m for i, m in enumerate(kill_ms)
                         if i != kill_step)
        median = healthy[len(healthy) // 2] if healthy else None
        return {
            "batch": batch, "vocab": vocab, "steps": steps,
            "kill_step": kill_step, "sim_net_ms": sim_net_ms,
            # the whole claim: a SIGKILL'd shard costs one paused step,
            # not a crashed worker and not a single wrong bit
            "bitwise_equal": kill_losses == base_losses,
            "recoveries": reg.counter("ps/recoveries").value - recov0,
            "rpc_retries": reg.counter("ps/rpc_retries").value - retry0,
            "recovery_pause_ms": (round(kill_ms[kill_step] - median, 1)
                                  if median is not None else None),
            "healthy_step_ms_p50": (round(median, 2)
                                    if median is not None else None),
            "baseline_step_ms_p50": round(
                sorted(base_ms)[len(base_ms) // 2], 2),
            "journal_bytes": int(reg.gauge(
                "ps/journal_bytes", table="fm_t").value),
        }
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_dispatch_overhead(on_tpu):
    """Per-step HOST overhead at batch-1 on a trivial train program, for
    the three dispatch strategies: `run` (one Python dispatch per step),
    `run_batched` (host-stacked K-step scan), and the `train_scanned`
    driver (DeviceLoader-fed K-step scan). The program body is one tiny
    fc+SGD update, so device compute is ~0 and wall/step ≈ what the host
    charges per step. Target: the scan driver's per-step cost < 5% of the
    per-step `run` cost (K amortizes dispatch, prefetch hides staging)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    k = 32
    reps = 4 if on_tpu else 2
    n = k * reps
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.fc(x, size=4)
        loss = layers.reduce_mean(y * y)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    feed = {"x": np.ones((1, 4), dtype=np.float32)}
    exe = fluid.Executor(fluid.TPUPlace())

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        run_s = _time_steps(exe, main_p, feed, loss, n)

        # run_batched: warm the K-step scan executable, then time reps
        # dispatches (same total step count as the run() loop)
        exe.run_batched(main_p, [feed] * k, fetch_list=[loss],
                        return_numpy=False)
        t0 = time.time()
        out = None
        for _ in range(reps):
            out = exe.run_batched(main_p, [feed] * k, fetch_list=[loss],
                                  return_numpy=False)
        np.asarray(out[0])
        batched_s = (time.time() - t0) / n

        # train_scanned: epoch of n feeds in K-step drains; first call
        # compiles, second is the measurement
        exe.train_scanned(main_p, reader=lambda: iter([feed] * n),
                          scan_steps=k, fetch_list=[loss])
        t0 = time.time()
        exe.train_scanned(main_p, reader=lambda: iter([feed] * n),
                          scan_steps=k, fetch_list=[loss])
        scan_s = (time.time() - t0) / n

    return {
        "k": k,
        "steps_timed": n,
        "run_us_per_step": round(run_s * 1e6, 1),
        "run_batched_us_per_step": round(batched_s * 1e6, 1),
        "scan_driver_us_per_step": round(scan_s * 1e6, 1),
        # the acceptance metric: scan-driver per-step host cost as a
        # percentage of the per-step dispatch path it replaces
        "scan_overhead_pct_of_run": round(100.0 * scan_s / run_s, 2),
        "run_batched_pct_of_run": round(100.0 * batched_s / run_s, 2),
        # the loader/staging cost the driver adds over a bare host-stacked
        # scan (run_batched) — the part peek_many is responsible for
        "scan_incremental_us_vs_batched": round((scan_s - batched_s) * 1e6,
                                                1),
        # On CPU the trivial step still costs ~100+ us of XLA compute per
        # step in EVERY strategy, so the pct is compute- not
        # dispatch-dominated; the <5% acceptance reading is the TPU run,
        # where this program's device time is ~0 and wall ≈ host overhead.
        "note": None if on_tpu else "cpu: pct dominated by per-step "
                                    "compute, not host dispatch",
    }


def _nmt_flops_per_batch(cfg, B, Ts, Tt):
    """Analytic matmul FLOPs (2mnk each) for one fwd pass of the enc-dec
    transformer; fwd+bwd ≈ 3× fwd. Padded positions DO run on the MXU, so
    this counts padded shapes — the honest non-pad tokens/s denominator then
    makes padding waste show up as lower MFU, exactly as it should."""
    d, dff, V = cfg.d_model, cfg.d_ff, cfg.tgt_vocab
    enc = cfg.n_enc * (8 * d * d * Ts          # qkvo projections
                       + 4 * d * Ts * Ts       # scores + probs·V
                       + 4 * d * dff * Ts)     # ffn
    dec = cfg.n_dec * (8 * d * d * Tt + 4 * d * Tt * Tt
                       + 8 * d * d * Tt + 4 * d * Tt * Ts   # cross-attn
                       + 4 * d * dff * Tt)
    out = 2 * d * V * Tt
    return 3 * B * (enc + dec + out)


def bench_nmt(on_tpu):
    """Transformer-big NMT train-step (BASELINE config 4): WMT-like
    variable-length stream packed into fixed-shape rows
    (reader.pack_by_tokens — VERDICT r3 #2: sequence packing through the
    segment-mask path replaces pure bucketing, so ONE compiled shape
    carries near-zero pad waste instead of 3 bucket programs carrying the
    bucket-boundary gap). Reports NON-PAD target tokens/s (the honest
    denominator) plus MFU on the packed shapes, the measured packer FILL
    RATE (r4 #8: recorded, not prose), and a SECOND packed shape
    (Ts=Tt=384) so the number doesn't live on one compiled shape.
    Returns (tokens/s, ms, mfu, n_shapes, shapes_dict)."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu import reader as preader
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import transformer_nmt as nmt

    if on_tpu:
        cfg = nmt.TransformerConfig()           # transformer-big
        shapes = [(256, 16, 24), (384, 12, 16)]  # (T, B, n_batches)
        max_sent = 128
    else:
        cfg = nmt.TransformerConfig(d_model=64, n_heads=4, d_ff=128,
                                    n_enc=2, n_dec=2, src_vocab=1000,
                                    tgt_vocab=1000)
        shapes = [(32, 4, 4)]
        max_sent = 24

    exe = fluid.Executor(fluid.TPUPlace())

    def _opt_factory():
        return mp.decorate(fluid.optimizer.Adam(1e-4), dtype="bfloat16",
                           use_dynamic_loss_scaling=False)

    def run_shape(T, B, n_batches, ab=False):
        Ts = Tt = T
        rng = np.random.RandomState(0)

        def sample_stream():
            # WMT14 en-de-like lengths: log-normal, mean ≈ 26 tokens
            for _ in range(200000):
                ls = int(np.clip(rng.lognormal(3.1, 0.55), 4, max_sent))
                lt = int(np.clip(ls * rng.uniform(0.8, 1.25), 4, max_sent))
                src = rng.randint(1, cfg.src_vocab, ls).astype("int32")
                tgt = rng.randint(1, cfg.tgt_vocab, lt).astype("int32")
                yield (src, tgt)

        packer = preader.pack_by_tokens(sample_stream, Ts, Tt)
        # kernel campaign: the headline arm feeds the block-sparse packed
        # flash-attention kernels the compact [B, T] segment rows instead
        # of materialized [B, T, T] masks; PDTPU_NMT_ATTN=dense reverts.
        attn_mode = os.environ.get("PDTPU_NMT_ATTN", "sparse")
        main_p, startup, feeds, loss = nmt.build_train_program(
            cfg, Ts, Tt, packed=True, attn=attn_mode,
            optimizer_factory=_opt_factory)
        exe.run(startup)

        def to_feed(stack, mode):
            feed = {"src_ids": stack["src_ids"], "tgt_ids": stack["tgt_ids"],
                    "lbl_ids": stack["lbl_ids"][..., None],
                    "src_pos": stack["src_pos"], "tgt_pos": stack["tgt_pos"]}
            if mode == "sparse":
                feed["src_seg"] = stack["src_seg"]
                feed["tgt_seg"] = stack["tgt_seg"]
            else:
                em, dm, cm = preader.packed_attention_masks(
                    stack["src_seg"], stack["tgt_seg"])
                feed.update(src_mask=em, tgt_mask=dm, cross_mask=cm)
            return feed

        def make_batches():
            rows = []
            for row in packer():
                rows.append(row)
                if len(rows) == B:
                    yield rows
                    rows = []

        batches = []
        first_stack = None
        fill_tgt = fill_src = 0
        for rows in make_batches():
            stack = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
            if first_stack is None:
                first_stack = stack
            non_pad = int((stack["lbl_ids"] != 0).sum())
            fill_tgt += int((stack["tgt_seg"] != 0).sum())
            fill_src += int((stack["src_seg"] != 0).sum())
            batches.append((to_feed(stack, attn_mode), non_pad))
            if len(batches) >= n_batches:
                break

        # pre-compile HBM planning: pick (sharding stage, remat policy,
        # microbatch K) that fits the device budget BEFORE paying the real
        # compile. Unconstrained backends (CPU smoke) get the baseline
        # plan without any candidate compiles.
        from paddle_tpu import planner
        plan = planner.plan_for(main_p, feed=batches[0][0],
                                loss_name=loss.name,
                                where=f"bench/nmt_big T={T}")
        prog = planner._compiled_for(main_p, loss.name, plan)
        K = plan.microbatch

        def micro_feeds(feed):
            if K <= 1:
                return [feed]
            return [{k: v[i * (v.shape[0] // K):(i + 1) * (v.shape[0] // K)]
                     for k, v in feed.items()} for i in range(K)]

        # stage feeds on device and warm up (compile) the packed shape —
        # off the clock (a production pipeline keeps batches prefetched)
        staged = [([{k: jnp.asarray(v) for k, v in mf.items()}
                    for mf in micro_feeds(feed)], non_pad)
                  for feed, non_pad in batches]
        with planner.guard(f"bench/nmt_big T={T}", plan=plan):
            exe.run(prog, feed=staged[0][0][0], fetch_list=[loss])
            exe.run(prog, feed=staged[0][0][0], fetch_list=[loss])

            t0 = time.time()
            total_tok = 0
            out = None
            for mfs, non_pad in staged:
                for mf in mfs:
                    out = exe.run(prog, feed=mf, fetch_list=[loss],
                                  return_numpy=False)
                total_tok += non_pad
            np.asarray(out[0])
            dt = time.time() - t0
        total_flops = len(staged) * _nmt_flops_per_batch(cfg, B, Ts, Tt)
        n = len(staged)
        # shared attribution: MFU and the matmul-floor roofline fraction
        # from the same code path every compiled program reports through.
        # The calibration comes from the shared disk cache the first
        # section child wrote, not a re-measure.
        from paddle_tpu.observability import perf
        calib = _calibration()
        att = perf.attribute(flops=total_flops, seconds=dt, calib=calib)
        per_kernel = None
        if on_tpu:
            try:
                from paddle_tpu.tools.roofline import capture_kernel_table
                per_kernel = capture_kernel_table(
                    lambda: exe.run(prog, feed=staged[0][0][0],
                                    fetch_list=[loss]), calib.floors)
            except Exception as e:  # trace plumbing must not kill the bench
                per_kernel = {"error": str(e)[:120]}
        # dense-mask vs block-sparse A/B on the same packed batch — both
        # arms run the plain (unplanned) program so the comparison isolates
        # the attention lowering, not the planner's remat/microbatch choice
        sparse_speedup = None
        if ab:
            ab_ms = {}
            for mode in ("dense", "sparse"):
                p2, s2, _, l2 = nmt.build_train_program(
                    cfg, Ts, Tt, packed=True, attn=mode,
                    optimizer_factory=_opt_factory)
                f2 = {k: jnp.asarray(v)
                      for k, v in to_feed(first_stack, mode).items()}
                with fluid.scope_guard(fluid.Scope()):
                    exe.run(s2)
                    ab_ms[mode] = _time_steps(exe, p2, f2, l2,
                                              6 if on_tpu else 2)
            sparse_speedup = round(ab_ms["dense"] / ab_ms["sparse"], 4)
        return {"T": T, "batch": B,
                "attn": attn_mode,
                "hbm_plan": plan.to_dict(),
                "tokens_per_sec": round(total_tok / dt, 1),
                "step_ms": round(dt / n * 1e3, 2),
                "mfu": round(att["mfu"], 4),
                "roofline_frac": round(att["roofline_fraction"], 4),
                "calibration_source": calib.source,
                "fill_rate_tgt": round(fill_tgt / (n * B * Tt), 4),
                "fill_rate_src": round(fill_src / (n * B * Ts), 4),
                "per_kernel": per_kernel,
                "sparse_speedup": sparse_speedup}

    results = [run_shape(*s, ab=(i == 0)) for i, s in enumerate(shapes)]
    best = results[0]
    return (best["tokens_per_sec"], best["step_ms"], best["mfu"],
            len(results), results, best.get("sparse_speedup"))


def _bench_ring_attn(extras2):
    """Pallas ring-attention arms in their own frame: the 4×16×4096×64
    bf16 q/k/v and the four jitted arms die when this returns, so the
    section's ~RESOURCE_EXHAUSTED ceiling can't leak into later sections
    (they used to live in main()'s frame until process exit)."""
    import importlib
    import statistics

    import jax as _jax
    import jax.numpy as _jnp
    from jax.sharding import Mesh as _Mesh
    from paddle_tpu import planner as _planner
    _RA = importlib.import_module(
        "paddle_tpu.parallel.ring_attention")
    # batch ladder under the footprint planner: prefer the full 4-row
    # batch, halve until the analytic live-bytes estimate fits the HBM
    # budget. The chosen plan rides in the doc (and in any OOM record the
    # section guard emits) so a residual RESOURCE_EXHAUSTED names it.
    _cands = []
    for _K in (1, 2, 4):
        _b = max(1, 4 // _K)
        _per_buf = _b * 16 * 4096 * 64 * 2   # one bf16 [b, 16, 4096, 64]
        # q/k/v + their grads + out + saved fwd residuals + working copies
        _cands.append((_planner.Plan(0, "none", _K), 12 * _per_buf))
    _plan = _planner.plan_for_footprint(_cands, where="bench/ring_attn")
    _B = max(1, 4 // _plan.microbatch)
    extras2["ring_attn_hbm_plan"] = _plan.to_dict()
    _mesh1 = _Mesh(np.array(_jax.devices()[:1]), ("sp",))
    _key = _jax.random.PRNGKey(0)
    _q, _k, _v = (_jax.random.normal(kk, (_B, 16, 4096, 64),
                                     _jnp.bfloat16)
                  for kk in _jax.random.split(_key, 3))
    _fns = {impl: _jax.jit(
        lambda q, k, v, impl=impl: _RA.ring_self_attention(
            q, k, v, _mesh1, causal=True, impl=impl))
        for impl in ("jnp", "pallas")}
    # fwd+bwd arms (VERDICT r4 #3: the Pallas ring BACKWARD —
    # per-block dq/dkv kernels — vs the oracle vjp)
    _gfns = {impl: _jax.jit(_jax.grad(
        lambda q, k, v, impl=impl: _RA.ring_self_attention(
            q, k, v, _mesh1, causal=True,
            impl=impl).astype(_jnp.float32).sum(),
        argnums=(0, 1, 2)))
        for impl in ("jnp", "pallas")}
    for f in _fns.values():  # compile all arms first
        np.asarray(f(_q, _k, _v).ravel()[0])
    for f in _gfns.values():
        np.asarray(f(_q, _k, _v)[0].ravel()[0])

    def _seg(fns, impl, iters=6):
        f = fns[impl]
        t0 = time.time()
        for _ in range(iters):
            o = f(_q, _k, _v)
        np.asarray(_jax.tree_util.tree_leaves(o)[0].ravel()[0])
        return (time.time() - t0) / iters * 1e3

    arms = {"jnp": [], "pallas": []}
    garms = {"jnp": [], "pallas": []}
    for _ in range(5):
        arms["jnp"].append(_seg(_fns, "jnp"))
        arms["pallas"].append(_seg(_fns, "pallas"))
        garms["jnp"].append(_seg(_gfns, "jnp", 3))
        garms["pallas"].append(_seg(_gfns, "pallas", 3))

    def _iqr(xs):
        qs = statistics.quantiles(xs, n=4)
        return round(qs[2] - qs[0], 3)

    med = {k: statistics.median(v) for k, v in arms.items()}
    gmed = {k: statistics.median(v) for k, v in garms.items()}
    ring_speedup = round(med["jnp"] / med["pallas"], 2)
    extras2["ring_attn_pallas_ms"] = {
        "median": round(med["pallas"], 3),
        "iqr": _iqr(arms["pallas"]), "n_segments": 5}
    extras2["ring_attn_oracle_ms"] = {
        "median": round(med["jnp"], 3), "iqr": _iqr(arms["jnp"])}
    extras2["ring_attn_bwd_pallas_ms"] = {
        "median": round(gmed["pallas"], 3),
        "iqr": _iqr(garms["pallas"]), "n_segments": 5}
    extras2["ring_attn_bwd_oracle_ms"] = {
        "median": round(gmed["jnp"], 3), "iqr": _iqr(garms["jnp"])}
    extras2["ring_attn_bwd_pallas_speedup_t4k"] = round(
        gmed["jnp"] / gmed["pallas"], 2)
    return ring_speedup


def bench_ckpt_integrity():
    """Crash-consistency tax: blocking save (fsync + sha256 manifest),
    manifest verify, and fallback restore wall time for a ~34 MB bundle,
    plus the per-call cost of an idle fault_point (the chaos probes ride
    in every hot loop — dispatch, reader pulls — so the idle cost must
    stay negligible: one env lookup + a lock, ~1 us)."""
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import faults
    from paddle_tpu.parallel.checkpoint import Checkpointer

    out = {}
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        faults.fault_point("bench.idle")
    out["idle_probe_ns"] = round((time.perf_counter() - t0) / n * 1e9, 1)

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data("x", [1024])
        h = fluid.layers.fc(x, 4096)
        h = fluid.layers.fc(h, 1024)
        fluid.layers.mean(h)
    d = tempfile.mkdtemp(prefix="pdtpu_ckpt_bench_")
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            ck = Checkpointer(d)
            t0 = time.perf_counter()
            ck.save(1, program=main_p, blocking=True)
            out["save_blocking_ms"] = round((time.perf_counter() - t0) * 1e3,
                                            2)
            t0 = time.perf_counter()
            ck.save(2, program=main_p)  # async: time to regain control
            out["save_dispatch_ms"] = round((time.perf_counter() - t0) * 1e3,
                                            2)
            ck.wait()
            t0 = time.perf_counter()
            bad = ck.verify(2)
            out["verify_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
            out["verify_clean"] = not bad
            t0 = time.perf_counter()
            ck.restore(program=main_p)
            out["restore_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
            out["bundle_mb"] = round(sum(
                os.path.getsize(os.path.join(d, f))
                for f in os.listdir(d)) / 1e6, 1)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out


def bench_serving_fleet(on_tpu):
    """Serving-fleet economics, the three arms the subsystem claims:
    (a) 1-replica vs N-replica closed-loop throughput, (b) the
    client-visible pause of a zero-downtime weight swap under sustained
    load (max gap between consecutive completions while the rollout
    runs, plus error/drop counts — both must be zero), (c) PS-backed CTR
    serving (cache-sized replica pulling rows from a live ShardedTable)
    vs the local-table Predictor, with the bitwise-identity flag and the
    resident-bytes fraction."""
    import shutil
    import tempfile
    import threading

    import paddle_tpu as fluid
    from paddle_tpu.tools import serving_bench as sb

    out = {}
    in_dim, hidden, n_req = (512, 2048, 256) if on_tpu else (64, 128, 96)
    buckets = (1, 2, 4, 8)
    dirs = [tempfile.mkdtemp(prefix=f"fleet_bench_v{i}_") for i in (1, 2)]
    dps = tempfile.mkdtemp(prefix="fleet_bench_ps_")
    dobs = tempfile.mkdtemp(prefix="fleet_bench_obs_")
    try:
        # -- (a) scale-out: one served replica vs a 3-replica fleet
        pred = sb.build_predictor(model_dir=dirs[0], in_dim=in_dim,
                                  hidden=hidden)
        rows = sb._gen_rows(n_req, in_dim)
        served = sb.bench_served(pred, rows, concurrency=16,
                                 buckets=buckets, batch_delay_ms=1.0)
        fleet3 = sb.bench_fleet(dirs[0], rows, replicas=3, concurrency=16,
                                buckets=buckets, batch_delay_ms=1.0)
        out["one_replica_rps"] = round(served["throughput_rps"], 1)
        out["fleet3_rps"] = round(fleet3["throughput_rps"], 1)
        out["fleet3_p99_ms"] = round(fleet3["p99_ms"], 2)
        out["fleet3_errors"] = fleet3["errors"]
        out["scaleout_speedup"] = round(
            fleet3["throughput_rps"]
            / max(served["throughput_rps"], 1e-9), 2)

        # -- (b) swap-under-load pause: one client hammers the fleet
        # while every replica warms + flips to v2; the "pause" is the
        # longest gap between consecutive completions
        sb.build_predictor(model_dir=dirs[1], in_dim=in_dim, hidden=hidden)
        from paddle_tpu.serving import fleet as fleet_mod
        reg = fleet_mod.ModelRegistry()
        reg.register("v1", dirs[0])
        reg.register("v2", dirs[1])
        fl = fleet_mod.ServingFleet(
            reg, "v1", replicas=3, buckets=buckets,
            server_kwargs={"max_batch_delay_ms": 1.0,
                           "max_queue_size": 1024})
        stamps, errs = [], [0]
        done = threading.Event()

        def client():
            i = 0
            while not done.is_set():
                try:
                    fl.infer({"x": rows[i % len(rows)]})
                    stamps.append(time.monotonic())
                except Exception:
                    errs[0] += 1
                i += 1

        with fl:
            t = threading.Thread(target=client)
            t.start()
            time.sleep(0.3)
            rollout = fl.rollout("v2")
            time.sleep(0.3)
            done.set()
            t.join()
        gaps = np.diff(np.asarray(stamps)) * 1e3 if len(stamps) > 1 else [0.0]
        out["swap_under_load"] = {
            "rollout_wall_ms": round(rollout["wall_ms"], 1),
            "requests_completed": len(stamps),
            "max_completion_gap_ms": round(float(np.max(gaps)), 2),
            "errors": errs[0],
            "versions_live": rollout["version"],
        }

        # -- (c) PS-backed vs local-table CTR arm
        out["ps_vs_local"] = _bench_ps_serving_arm(dps, on_tpu)

        # -- (d) cross-process observability (ISSUE 13 acceptance cell):
        # router -> subprocess worker -> subprocess pservers, one merged
        # trace spanning all three process kinds + one federated scrape
        out["observability"] = _bench_fleet_observability_arm(dobs, on_tpu)
    finally:
        for d in dirs + [dps, dobs]:
            shutil.rmtree(d, ignore_errors=True)
    return out


def _bench_ps_serving_arm(workdir, on_tpu):
    """Per-request latency of the local-table Predictor vs the
    PsLookupPredictor (rows pulled from a live in-process ShardedTable
    through an LRU row cache), same checkpoint — plus the bitwise flag
    and the replica's resident-bytes fraction of the full table."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import inference, layers
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.initializer import RowPackInitializer
    from paddle_tpu.ops.deferred_rows import pack_rows
    from paddle_tpu.param_attr import ParamAttr
    from paddle_tpu.ps import RangeSpec, ShardedTable

    V, D, MULT, F, CAP = (65536, 8, 2, 16, 1024) if on_tpu \
        else (4096, 8, 2, 8, 256)

    def build_and_save(vocab_rows, model_dir, packed=None, dense=None):
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            ids = layers.data("ids", [F], dtype="int64")
            emb = layers.embedding(
                ids, [vocab_rows, D * MULT], is_sparse=True, row_pack=True,
                param_attr=ParamAttr(name="tb",
                                     initializer=RowPackInitializer(
                                         D, D * MULT, -1.0, 1.0)))
            emb = layers.slice(emb, axes=[2], starts=[0], ends=[D])
            r = layers.reshape(emb, [-1, F * D])
            out_v = layers.fc(r, 16, act="softmax")
        exe = fluid.Executor(fluid.TPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            sc = global_scope()
            if packed is not None:
                sc.set_var("tb", jnp.asarray(packed))
                # by dtype, not np.asarray: on a TPU the scope's RNG state
                # is a typed key, which does not convert to numpy
                dense = {n: np.asarray(sc.find_var(n))
                         for n in sc.var_names()
                         if n != "tb"
                         and sc.find_var(n).dtype == jnp.float32}
            else:
                for n, v in dense.items():
                    sc.set_var(n, jnp.asarray(v))
                sc.set_var("tb", jnp.zeros((vocab_rows, 128), jnp.uint16))
            fluid.io.save_inference_model(model_dir, ["ids"], [out_v],
                                          exe, main_p)
        return dense

    vis = np.random.RandomState(7).uniform(-1, 1, (V, D)).astype("float32")
    full = np.zeros((V, D * MULT), "float32")
    full[:, :D] = vis
    packed = np.asarray(pack_rows(jnp.asarray(full)))
    d_local = os.path.join(workdir, "local")
    d_ps = os.path.join(workdir, "ps")
    dense = build_and_save(V, d_local, packed=packed)
    build_and_save(CAP, d_ps, dense=dense)

    ref = inference.create_predictor(inference.Config(d_local))
    table = ShardedTable.build_in_process("tb", RangeSpec.even(V, 3),
                                          full_rows=packed)
    try:
        ps = inference.PsLookupPredictor(
            inference.create_predictor(inference.Config(d_ps)),
            [inference.PsLookupBinding("tb", table, ["ids"])],
            cache_rows_per_table=2 * CAP)
        rng = np.random.RandomState(3)
        batches = [rng.randint(0, V, size=(8, F)).astype(np.int64)
                   for _ in range(32)]
        ref.run_padded({"ids": batches[0]}, 8)   # compile outside clocks
        ps.run_padded({"ids": batches[0]}, 8)
        bitwise = True
        t_local = t_ps = 0.0
        for ids in batches:
            t0 = time.perf_counter()
            o_ref = ref.run_padded({"ids": ids}, 8)
            t_local += time.perf_counter() - t0
            t0 = time.perf_counter()
            o_ps = ps.run_padded({"ids": ids}, 8)
            t_ps += time.perf_counter() - t0
            for a, b in zip(o_ref, o_ps):
                if not (np.asarray(a) == np.asarray(b)).all():
                    bitwise = False
        st = ps.stats()["tb"]
        return {
            "bitwise_identical": bitwise,
            "local_ms_per_req": round(t_local / len(batches) * 1e3, 3),
            "ps_ms_per_req": round(t_ps / len(batches) * 1e3, 3),
            "lookup_overhead_x": round(t_ps / max(t_local, 1e-12), 2),
            "cache": {k: st[k] for k in ("hits", "misses", "evictions")},
            "resident_bytes": ps.resident_table_bytes(),
            "full_table_bytes": int(packed.nbytes),
            "resident_fraction": round(
                ps.resident_table_bytes() / packed.nbytes, 4),
        }
    finally:
        table.close()


def _bench_fleet_observability_arm(workdir, on_tpu):
    """The ISSUE-13 acceptance cell at bench scale: requests routed
    through a FleetRouter to a SUBPROCESS worker whose PsLookupPredictor
    pulls rows from two SUBPROCESS pservers — three distinct process
    kinds on one request path. Measures (1) how many traces span >=3
    processes in the merged chrome trace (one trace_id, flow arrows) and
    (2) that a single federated scrape carries the pull-latency
    percentiles and serving queue depth labeled per shard/replica. Both
    artifacts are written as sidecars (`_telemetry_out`)."""
    import subprocess

    import paddle_tpu as fluid
    from paddle_tpu import inference, layers
    from paddle_tpu.initializer import RowPackInitializer
    from paddle_tpu.observability.federate import (FederatedScraper,
                                                   ScrapeTarget)
    from paddle_tpu.observability.tracer import get_tracer
    from paddle_tpu.param_attr import ParamAttr
    from paddle_tpu.ps import RangeSpec, SocketClient
    from paddle_tpu.serving.fleet.registry import ModelRegistry
    from paddle_tpu.serving.fleet.replica import ProcessReplica
    from paddle_tpu.serving.fleet.router import FleetRouter
    from paddle_tpu.tools.timeline import merge_fleet_traces

    V, D, MULT, F, CAP = (65536, 8, 2, 16, 1024) if on_tpu \
        else (4096, 8, 2, 8, 256)
    n_req = 24

    # cache-sized model dir: the worker holds CAP rows of `tb`, every
    # miss is a live pull from the pservers (that socket hop is the
    # cross-process edge under test)
    d_model = os.path.join(workdir, "obs_model")
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        ids = layers.data("ids", [F], dtype="int64")
        emb = layers.embedding(
            ids, [CAP, D * MULT], is_sparse=True, row_pack=True,
            param_attr=ParamAttr(name="tb",
                                 initializer=RowPackInitializer(
                                     D, D * MULT, -1.0, 1.0)))
        emb = layers.slice(emb, axes=[2], starts=[0], ends=[D])
        r = layers.reshape(emb, [-1, F * D])
        out_v = layers.fc(r, 16, act="softmax")
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d_model, ["ids"], [out_v], exe,
                                      main_p)

    # two real pserver subprocesses (zero-initialized rows are fine —
    # the arm measures the observability plane, not the predictions)
    runner = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "ps_server_runner.py")
    spec = RangeSpec.even(V, 2)
    procs, eps = [], []
    router = rep = None
    try:
        for i in range(2):
            lo, hi = spec.bounds(i)
            p = subprocess.Popen(
                [sys.executable, runner, "--port", "0",
                 "--table", f"tb:{lo}:{hi}"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True)
            ep = p.stdout.readline().strip()
            if not ep:
                raise RuntimeError("pserver runner died at boot")
            procs.append(p)
            eps.append(ep)

        mv = ModelRegistry().register("obs", d_model)
        # this process holds the chip, so the worker it starts is pinned to
        # the CPU: serving/fleet workers are a CPU-mesh-only tier today
        rep = ProcessReplica(
            "obs-replica", mv, buckets=(1, 2, 4, 8),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            extra_args=["--ps-endpoints", ",".join(eps),
                        "--ps-table", f"tb=tb:{V}",
                        "--ps-id-feeds", "ids",
                        "--ps-cache-rows", str(2 * CAP)],
            server_kwargs={"max_batch_delay_ms": 1.0})
        router = FleetRouter([rep])
        # scope the coordinator trace to this arm (earlier fleet arms
        # share the process tracer)
        get_tracer().clear()
        rng = np.random.RandomState(11)
        t0 = time.perf_counter()
        for _ in range(n_req):
            router.infer(
                {"ids": rng.randint(0, V, size=(8, F)).astype(np.int64)})
        wall = time.perf_counter() - t0

        # -- (1) merge the three processes' chrome traces
        traces = [("router", get_tracer().export_chrome_trace()),
                  ("replica", rep.trace_export())]
        for i, ep in enumerate(eps):
            c = SocketClient(ep, retries=0)
            try:
                traces.append((f"pserver{i}", c.trace_export()))
            finally:
                c.close()
        merged = merge_fleet_traces([t for _, t in traces],
                                    [n for n, _ in traces])
        procs_per_trace = {}
        for name, tr in traces:
            for ev in tr.get("traceEvents", []):
                tid = (ev.get("args") or {}).get("trace_id")
                if tid and ev.get("ph") in ("B", "X", "i"):
                    procs_per_trace.setdefault(tid, set()).add(name)
        spans3 = [len(v) for v in procs_per_trace.values() if len(v) >= 3]
        flows = sum(1 for ev in merged["traceEvents"]
                    if ev.get("ph") in ("s", "f"))

        # -- (2) one federated scrape over all four processes
        fed = FederatedScraper(
            [ScrapeTarget.local(name="router", role="coordinator"),
             ScrapeTarget.call(rep.metrics, name="obs-replica",
                               role="replica-process")]
            + [ScrapeTarget.ps(ep, shard=i)
               for i, ep in enumerate(eps)]).scrape_once()
        pull_p99, queue_depth = {}, {}
        for t in fed["targets"]:
            for s in t["series"]:
                if (s["name"] == "ps/shard_pull_ms"
                        and s.get("type") == "summary"):
                    sh = (s.get("labels") or {}).get("shard", "?")
                    pull_p99[f"shard={sh}"] = round(
                        (s.get("summary") or {}).get("p99", 0.0), 2)
                elif s["name"] == "serving/queue_depth":
                    queue_depth[t["process"]] = s.get("value")

        return {
            "requests": n_req,
            "rps": round(n_req / wall, 1),
            "replica_platform": "cpu",
            "processes_traced": [n for n, _ in traces],
            # the acceptance numbers: traces whose spans land in >=3
            # distinct processes, and the flow arrows linking them
            "cross_process_traces": len(spans3),
            "max_processes_one_trace": max(spans3, default=0),
            "flow_events": flows,
            "federated_ok": fed["ok"],
            "pull_p99_ms_by_shard": pull_p99,
            "queue_depth_by_process": queue_depth,
            "autoscale_signals": fed.get("signals"),
            "trace_sidecar": _telemetry_out("serving_fleet", "trace",
                                            merged),
            "metrics_sidecar": _telemetry_out("serving_fleet", "metrics",
                                              fed),
        }
    finally:
        if router is not None:
            router.close()
        if rep is not None:
            try:
                rep.stop()
            except Exception:
                pass
        for p in procs:
            p.kill()
            p.wait()


def bench_inference_compiler(on_tpu):
    """Inference-compiler economics (PR 16), three cells: (a) the
    Program-IR pass pipeline's win attributed PER PASS through the perf
    CostLedger (ops removed / flops / bytes deltas, wall_ms — the same
    report `predictor.pass_report` carries); (b) int8 post-training
    quantization vs bf16 served throughput on the same model bytes at
    matched accuracy (the calibration gate runs first; its measured
    delta is recorded). The ≥1.7x int8-over-bf16 contract is asserted on
    TPU, where the int8 matmul actually changes the MXU/HBM economics —
    a CPU host emulates int8 matmuls in int32 and may show none of it,
    so `speedup_target_met` stays None off-TPU; (c) N=3 tenant
    co-hosting on one fleet under mixed weighted load, each tenant
    holding its own p99 SLO (the serving_bench --models machinery)."""
    import shutil
    import tempfile

    from paddle_tpu import inference
    from paddle_tpu.observability import perf
    from paddle_tpu.tools import serving_bench as sb

    in_dim, hidden, n_req = (512, 2048, 256) if on_tpu else (64, 256, 96)
    buckets = (1, 2, 4, 8)
    slo_ms = 500.0 if on_tpu else 10_000.0
    d = tempfile.mkdtemp(prefix="infcomp_bench_")
    out = {}
    try:
        rows = sb._gen_rows(n_req, in_dim)
        calib_feeds = [{"x": r} for r in rows[:8]]
        pred32 = sb.build_predictor(model_dir=d, in_dim=in_dim,
                                    hidden=hidden)

        # -- (a) per-pass attribution, straight from the ledger
        rep = pred32.pass_report
        out["pass_pipeline"] = {
            "label": rep["label"],
            "ops_total_removed": rep["ops_total_removed"],
            "flops_total_delta": rep["flops_total_delta"],
            "bytes_total_delta": rep["bytes_total_delta"],
            "per_pass": [
                {"pass": r["pass"], "neutrality": r["neutrality"],
                 "ops_removed": r["ops_before"] - r["ops_after"],
                 "flops_delta": r["flops_delta"],
                 "bytes_delta": r["bytes_delta"],
                 "wall_ms": r["wall_ms"]} for r in rep["passes"]],
            "in_ledger": perf.get_ledger().pass_reports().get(
                rep["label"]) is not None,
        }

        # -- (b) int8 vs bf16 served throughput, same model bytes, same
        # load; the int8 predictor records its gated accuracy delta
        arms = {}
        for prec in ("bf16", "int8"):
            p = inference.create_predictor(
                sb._make_config(d, prec, calib_feeds))
            r = sb.bench_served(p, rows, concurrency=16, buckets=buckets,
                                batch_delay_ms=1.0)
            arms[prec] = {"rps": round(r["throughput_rps"], 1),
                          "p99_ms": round(r["p99_ms"], 2),
                          "errors": r["errors"]}
            if prec == "int8":
                qm = p.quant_meta
                arms[prec]["accuracy_delta"] = round(
                    qm["accuracy_delta"], 6)
                arms[prec]["accuracy_budget"] = qm["accuracy_budget"]
        speedup = round(arms["int8"]["rps"]
                        / max(arms["bf16"]["rps"], 1e-9), 2)
        out["int8_vs_bf16"] = {
            **{f"{k}_{m}": v for k, a in arms.items()
               for m, v in a.items()},
            "speedup": speedup,
            # the acceptance bar is a TPU statement: int8 halves the
            # weight bytes and doubles MXU rate there; a CPU int32
            # emulation can even run slower
            "speedup_target": 1.7,
            "speedup_target_met": (speedup >= 1.7) if on_tpu else None,
        }

        # -- (c) N=3 tenants, weighted mixed load, per-tenant p99 SLO
        ten = sb.bench_tenants(
            d, {"ads": 2.0, "feed": 1.0, "search": 1.0}, rows,
            replicas=4, concurrency=16, buckets=buckets,
            batch_delay_ms=1.0, precision="int8",
            calib_feeds=calib_feeds, slo_p99_ms=slo_ms)
        per_tenant = {
            name: {"p99_ms": round(trow["p99_ms"], 2),
                   "requests": trow["requests"],
                   "errors": trow["errors"],
                   "throttled": trow["throttled"],
                   "slo_ok": (trow["router"] or {}).get("slo_ok")}
            for name, trow in ten["per_tenant"].items()}
        out["tenancy"] = {
            "slo_p99_ms": slo_ms,
            "rps": round(ten["throughput_rps"], 1),
            "tenants": per_tenant,
            "all_slo_ok": all(t["slo_ok"] for t in per_tenant.values()),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out


def bench_online_learning(on_tpu):
    """Streaming online learning (ISSUE 14, paddle_tpu.streaming): one
    process trains a CTR model from an endless skewed stream through
    dynamic-vocab PS shards while a PsLookupPredictor serves lookups
    against the SAME tables. Reported: throughput, the AUC trajectory
    scored THROUGH the serving predictor (post-delta-push bytes), vocab
    churn (rows materialized/evicted per minute inside a slab smaller
    than the id space), incremental-checkpoint bytes vs the full save
    they chain on, and delta-push staleness p50/p99 vs the budget."""
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import inference, layers
    from paddle_tpu.initializer import RowPackInitializer
    from paddle_tpu.param_attr import ParamAttr
    from paddle_tpu.parallel.checkpoint import Checkpointer
    from paddle_tpu.ps import (InProcessClient, PsEmbeddingTier,
                               PsTableBinding, RangeSpec, ShardedTable,
                               make_dynamic_shards)
    from paddle_tpu.streaming import (DeltaPublisher, OnlineTrainer,
                                      StreamingDataset, eval_auc)

    vocab, cap_per_shard, steps, batch = ((200_000, 16_384, 600, 256)
                                          if on_tpu
                                          else (8_000, 768, 400, 16))
    fields, d, mult = 8, 8, 2
    rows_per_step = batch * fields
    hot_ids = max(64, vocab // 40)
    staleness_s = 1.0

    rng = np.random.RandomState(17)
    w = rng.uniform(-1.0, 1.0, vocab)

    def source():
        g = np.random.RandomState(18)
        while True:
            if g.uniform() < 0.9:
                ids = g.randint(0, hot_ids, fields)
            else:
                ids = g.randint(0, vocab, fields)
            lbl = 1.0 if w[ids].sum() > 0 else 0.0
            yield {"ids": ids.astype("int64"),
                   "lbl": np.array([lbl], "float32")}

    def build(vocab_rows, train):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ids = layers.data("ids", [fields], dtype="int64")
            emb = layers.embedding(
                ids, [vocab_rows, d * mult], is_sparse=True, row_pack=True,
                param_attr=ParamAttr(name="ol_t",
                                     initializer=RowPackInitializer(
                                         d, d * mult, -0.01, 0.01)))
            emb = layers.slice(emb, axes=[2], starts=[0], ends=[d])
            score = layers.reshape(layers.reduce_sum(emb, dim=[1, 2]),
                                   [-1, 1])
            if not train:
                return main, startup, ids, score
            lbl = layers.data("lbl", [1], dtype="float32")
            loss = layers.mean(layers.square_error_cost(score, lbl))
            fluid.optimizer.Adagrad(
                0.1,
                packed_rows={"rows_per_step": rows_per_step}).minimize(loss)
        return main, startup, None, loss

    workdir = tempfile.mkdtemp(prefix="pdtpu_online_")
    spec = RangeSpec.even(vocab, 2)
    shards = make_dynamic_shards("ol_t", spec,
                                 capacity_per_shard=cap_per_shard,
                                 high_watermark=0.9, low_watermark=0.7,
                                 keep_freq=3)
    table = ShardedTable("ol_t", spec,
                         [InProcessClient([s]) for s in shards])
    try:
        # serving half: saved inference model + PS-backed predictor fed
        # by the delta stream
        imain, istart, iids, iscore = build(rows_per_step, train=False)
        iexe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            iexe.run(istart)
            fluid.io.save_inference_model(
                os.path.join(workdir, "m"), ["ids"], [iscore], iexe, imain)
        base = inference.create_predictor(
            inference.Config(os.path.join(workdir, "m")))
        ps = inference.PsLookupPredictor(
            base, [inference.PsLookupBinding("ol_t", table, ["ids"])],
            cache_rows_per_table=2 * cap_per_shard)
        pub = DeltaPublisher(table, staleness_s=staleness_s)
        pub.attach_predictor(ps)

        ds = StreamingDataset(source, batch_size=batch, held_out_every=7,
                              eval_window=64 * batch)
        main, startup, _, loss = build(rows_per_step, train=True)
        exe = fluid.Executor(fluid.TPUPlace() if on_tpu
                             else fluid.CPUPlace())
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(startup)
            ck = Checkpointer(os.path.join(workdir, "ck"), keep=4)
            ck.save(0, program=main, scope=sc, blocking=True,
                    ps_tables={"ol_t": table})
            tier = PsEmbeddingTier(
                main, [PsTableBinding("ol_t", table, ["ids"])],
                pull_ahead=1, push_depth=0)
            trainer = OnlineTrainer(
                exe, main, tier, ds, fetch_list=[loss], scope=sc,
                ps_tables={"ol_t": table}, checkpointer=ck,
                publishers=[pub],
                sweep_every=max(10, steps // 10),
                delta_every=max(10, steps // 8), compact_every=4,
                eval_every=max(10, steps // 8),
                eval_fn=lambda: eval_auc(
                    ds, lambda f: ps.run({"ids": f["ids"]})[0], "lbl"))
            t0 = time.time()
            try:
                trainer.run(max_steps=steps)
                trainer.finish()
            finally:
                elapsed = time.time() - t0
                tier.close()
                pub.close()

        sstats = [s.stats() for s in shards]
        mat = sum(s["materialized"] for s in sstats)
        evicted = sum(s["evicted"] for s in sstats)
        live = sum(s["live_rows"] for s in sstats)
        full_b = sum(os.path.getsize(os.path.join(workdir, "ck", f))
                     for f in os.listdir(os.path.join(workdir, "ck"))
                     if f.startswith("ckpt-") and f.endswith(".pkl"))
        deltas = [os.path.getsize(os.path.join(workdir, "ck", f))
                  for f in os.listdir(os.path.join(workdir, "ck"))
                  if f.startswith("delta-") and f.endswith(".pkl")]
        aucs = [(s, round(v, 4))
                for s, v in trainer.history["eval"]
                if not np.isnan(v)]
        return {
            "steps": trainer.step,
            "rate": round(steps * batch / elapsed, 1),
            "auc_trajectory": aucs,
            "auc_final": aucs[-1][1] if aucs else None,
            "vocab_ids_seen": int(mat),
            "provisioned_rows": 2 * cap_per_shard,
            "live_rows": int(live),
            "rows_materialized_per_min": round(mat * 60.0 / elapsed, 1),
            "rows_evicted_per_min": round(evicted * 60.0 / elapsed, 1),
            "delta_saves": len(deltas),
            "delta_bytes_avg": (int(np.mean(deltas)) if deltas else None),
            "full_bytes": int(full_b),
            "delta_vs_full": (round(np.mean(deltas) / full_b, 4)
                              if deltas and full_b else None),
            "staleness_ms": pub.staleness_percentiles(),
            "staleness_budget_ms": staleness_s * 1e3,
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_slo_alerting(on_tpu):
    """SLO engine chaos cell (ISSUE 17): a small online-learning stack
    over REAL subprocess pservers — training pushes through the tier,
    a DeltaPublisher streams rows to a PsLookupPredictor, a ShardMonitor
    and FederatedScraper feed an SloEngine + AlertManager — then one
    pserver is SIGKILLed under load. Asserted end to end: the
    availability (``PsShardAvailability``) and staleness
    (``DeltaStaleness``) page alerts reach ``firing`` within two scrape
    sweeps of their condition first being observable, auto-``resolve``
    after the shard restarts and the tier recovers, and the
    alert-triggered flight dump names the dead shard."""
    import shutil
    import subprocess
    import sys
    import tempfile
    import threading

    import paddle_tpu as fluid
    from paddle_tpu import inference, layers
    from paddle_tpu.initializer import RowPackInitializer
    from paddle_tpu.observability import (AlertManager, FederatedScraper,
                                          ScrapeTarget, SloEngine, SloSpec,
                                          get_registry,
                                          install_alert_manager,
                                          install_scraper)
    from paddle_tpu.param_attr import ParamAttr
    from paddle_tpu.parallel.checkpoint import Checkpointer
    from paddle_tpu.ps import (PsEmbeddingTier, PsTableBinding, RangeSpec,
                               ShardedTable, ShardMonitor, SocketClient)
    from paddle_tpu.streaming import DeltaPublisher

    vocab, batch = (16_384, 256) if on_tpu else (4_000, 32)
    fields, d, mult = 8, 8, 2
    lanes = d * mult
    staleness_budget_ms = 1200.0
    sweep_s = 0.25          # scraper cadence
    dead_s = 1.6            # outage long enough to blow the budget
    # page windows compress to 5 s / ~0.42 s: a hard outage saturates
    # both within one bad sweep, exactly the multiwindow design intent
    window_scale = 1.0 / 720.0

    runner = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "ps_server_runner.py")
    spec = RangeSpec.even(vocab, 2)

    def launch(i, port=0):
        lo, hi = spec.bounds(i)
        p = subprocess.Popen(
            [sys.executable, runner, "--port", str(port),
             "--table", f"slo_t:{lo}:{hi}"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        ep = p.stdout.readline().strip()
        if not ep:
            raise RuntimeError("pserver runner died at boot")
        return p, ep

    # generous retry budget: the worker must survive a ~2 s outage
    # inside one push, then recover via the checkpoint+journal hook
    knobs = {"PDTPU_PS_RETRIES": "400", "PDTPU_PS_RETRY_BACKOFF_MS": "20",
             "PDTPU_PS_TIMEOUT": "10"}
    saved_env = {k: os.environ.get(k) for k in
                 list(knobs) + ["PDTPU_FLIGHT_DIR"]}
    workdir = tempfile.mkdtemp(prefix="pdtpu_bench_slo_")
    os.environ.update(knobs)
    os.environ["PDTPU_FLIGHT_DIR"] = os.path.join(workdir, "flight")

    def build(train):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ids = layers.data("ids", [fields], dtype="int64")
            emb = layers.embedding(
                ids, [batch * fields, lanes], is_sparse=True,
                row_pack=True,
                param_attr=ParamAttr(name="slo_t",
                                     initializer=RowPackInitializer(
                                         d, lanes, -0.01, 0.01)))
            emb = layers.slice(emb, axes=[2], starts=[0], ends=[d])
            score = layers.reshape(layers.reduce_sum(emb, dim=[1, 2]),
                                   [-1, 1])
            if not train:
                return main, startup, score
            lbl = layers.data("lbl", [1], dtype="float32")
            loss = layers.mean(layers.square_error_cost(score, lbl))
            fluid.optimizer.Adagrad(
                0.1, packed_rows={
                    "rows_per_step": batch * fields}).minimize(loss)
        return main, startup, loss

    reg = get_registry()
    procs, eps = [], []
    monitor = scraper = pub = tier = None
    stop_evt = threading.Event()
    train_err = []
    try:
        for i in range(2):
            p, ep = launch(i)
            procs.append(p)
            eps.append(ep)
        table = ShardedTable("slo_t", spec,
                             [SocketClient(ep) for ep in eps])

        # serving half
        imain, istart, iscore = build(train=False)
        iexe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            iexe.run(istart)
            fluid.io.save_inference_model(
                os.path.join(workdir, "m"), ["ids"], [iscore], iexe, imain)
        base = inference.create_predictor(
            inference.Config(os.path.join(workdir, "m")))
        ps = inference.PsLookupPredictor(
            base, [inference.PsLookupBinding("slo_t", table, ["ids"])],
            cache_rows_per_table=batch * fields)
        pub = DeltaPublisher(table, staleness_s=0.4)
        pub.attach_predictor(ps)

        # judgment layer: monitor -> scraper -> SLO engine -> alerts
        monitor = ShardMonitor(eps, interval_s=0.1).start()
        am = AlertManager(for_s=0.0, resolved_hold_s=600.0)
        install_alert_manager(am)
        events = []          # (wall_t, sweep_no, event) timeline
        sweeps = [0]
        first_bad = {}       # alert name -> sweep_no condition observable
        am.add_sink(lambda ev: events.append(
            (time.time(), sweeps[0], ev)))
        scraper = FederatedScraper(
            [ScrapeTarget.local()]
            + [ScrapeTarget.ps(ep, shard=i) for i, ep in enumerate(eps)],
            interval_s=sweep_s, timeout=0.5)

        def count_sweep(doc):
            sweeps[0] += 1
            for r in doc["targets"]:
                for s in r["series"]:
                    if (s.get("name") == "ps/shard_up"
                            and not s.get("value")
                            and "PsShardAvailability" not in first_bad):
                        first_bad["PsShardAvailability"] = sweeps[0]
                    if (s.get("name") == "staleness/last_visible_ts"
                            and s.get("value")
                            and (time.time() - s["value"]) * 1e3
                            > staleness_budget_ms
                            and "DeltaStaleness" not in first_bad):
                        first_bad["DeltaStaleness"] = sweeps[0]

        scraper.add_sweep_listener(count_sweep)
        engine = SloEngine(
            [SloSpec.floor("PsShardAvailability", "ps/shard_up", 1.0,
                           group_by="shard", objective=0.999),
             SloSpec.freshness("DeltaStaleness",
                               "staleness/last_visible_ts",
                               staleness_budget_ms, group_by="table",
                               objective=0.999)],
            alert_manager=am, window_scale=window_scale)
        engine.attach(scraper)
        install_scraper(scraper)

        # training load
        main, startup, loss = build(train=True)
        exe = fluid.Executor(fluid.CPUPlace())
        sc = fluid.Scope()
        rng = np.random.RandomState(23)
        with fluid.scope_guard(sc):
            exe.run(startup)
            ck = Checkpointer(os.path.join(workdir, "ck"))
            ck.save(0, program=main, scope=sc, blocking=True,
                    ps_tables={"slo_t": table})
            tier = PsEmbeddingTier(
                main, [PsTableBinding("slo_t", table, ["ids"])],
                pull_ahead=1, push_depth=0)
            tier.attach_checkpointer(ck)

            def feed_gen():
                while not stop_evt.is_set():
                    yield {"ids": rng.randint(
                               0, vocab, (batch, fields)).astype("int64"),
                           "lbl": rng.randint(
                               0, 2, (batch, 1)).astype("float32")}

            def train_loop():
                try:
                    for prep in tier.steps(lambda: feed_gen()):
                        tier.run_step(exe, prep, fetch_list=[loss])
                        if stop_evt.is_set():
                            break
                        time.sleep(0.03)
                except Exception as e:  # surfaced in the result doc
                    train_err.append(f"{type(e).__name__}: {e}")

            def serve_loop():
                while not stop_evt.is_set():
                    try:
                        ps.run({"ids": rng.randint(
                            0, vocab, (8, fields)).astype("int64")})
                    except Exception:
                        pass  # outage window: serving pulls block/fail
                    time.sleep(0.1)

            tthread = threading.Thread(target=train_loop, daemon=True)
            sthread = threading.Thread(target=serve_loop, daemon=True)
            tthread.start()
            sthread.start()
            scraper.start()

            time.sleep(2.0)                     # healthy baseline
            kill_t = time.time()
            kill_sweep = sweeps[0]
            procs[1].kill()
            procs[1].wait()
            port1 = int(eps[1].rsplit(":", 1)[1])
            time.sleep(dead_s)                  # the outage window
            procs[1], _ = launch(1, port=port1)

            # recovery + resolution tail: wait for both pages to clear
            deadline = time.time() + 20.0
            while time.time() < deadline:
                if not am.firing(severity="page"):
                    break
                time.sleep(0.25)
            time.sleep(3.0)  # let warn-severity windows drain too

            stop_evt.set()
            tthread.join(timeout=30.0)
            sthread.join(timeout=10.0)
            scraper.stop()
            tier.flush()
            tier.close()
            tier = None
            pub.close()
            pub = None

        # ------------------------------------------------ the assertions
        def fired(name):
            return [(t, sw, ev) for t, sw, ev in events
                    if ev["event"] == "firing" and ev["name"] == name
                    and ev["severity"] == "page" and t >= kill_t]

        avail = fired("PsShardAvailability")
        stale = fired("DeltaStaleness")
        assert avail, f"availability page never fired; events={events}"
        assert stale, f"staleness page never fired; events={events}"
        assert avail[0][2]["labels"].get("shard") == "1", avail[0][2]
        avail_sweeps = avail[0][1] - first_bad["PsShardAvailability"]
        stale_sweeps = stale[0][1] - first_bad["DeltaStaleness"]
        assert avail_sweeps <= 2, (
            f"availability took {avail_sweeps} sweeps past first bad "
            f"scrape (kill@{kill_sweep}, bad@{first_bad}, "
            f"fire@{avail[0][1]})")
        assert stale_sweeps <= 2, (
            f"staleness took {stale_sweeps} sweeps past first bad "
            f"scrape (bad@{first_bad}, fire@{stale[0][1]})")
        still_firing = [a.name for a in am.firing()]
        assert not still_firing, (
            f"alerts still firing after recovery: {still_firing}")
        page_states = {(a.name): a.state
                       for a in am.alerts(severity="page")}
        assert page_states.get("PsShardAvailability") == "resolved", (
            page_states)

        # the page's flight dump names the dead shard
        dump_path = avail[0][2].get("dump_path")
        assert dump_path and os.path.exists(dump_path), avail[0][2]
        with open(dump_path) as f:
            dump = json.load(f)
        assert dump["context"]["alert"] == "PsShardAvailability", (
            dump["context"])
        assert dump["context"]["labels"].get("shard") == "1", (
            dump["context"])

        # e2e staleness audit populated (publisher stamp -> serving
        # visibility), and the resolve round-trip timing
        e2e = ps.staleness_e2e_percentiles()
        assert e2e["p50"] is not None, "staleness/e2e_ms never populated"
        resolve_ev = [(t, sw, ev) for t, sw, ev in events
                      if ev["event"] == "resolved"
                      and ev["name"] == "PsShardAvailability"
                      and ev["severity"] == "page"]
        return {
            "vocab": vocab, "batch": batch,
            "sweep_s": sweep_s, "window_scale": window_scale,
            "outage_s": dead_s,
            "staleness_budget_ms": staleness_budget_ms,
            "avail_fire_sweeps_past_bad": int(avail_sweeps),
            "stale_fire_sweeps_past_bad": int(stale_sweeps),
            "avail_fire_after_kill_ms": round(
                (avail[0][0] - kill_t) * 1e3, 1),
            "stale_fire_after_kill_ms": round(
                (stale[0][0] - kill_t) * 1e3, 1),
            "page_resolved_after_kill_ms": (round(
                (resolve_ev[0][0] - kill_t) * 1e3, 1)
                if resolve_ev else None),
            "total_alert_events": len(events),
            "staleness_e2e_ms": e2e,
            "flight_dump_names_shard": dump["context"]["labels"]["shard"],
            "train_error": train_err[0] if train_err else None,
            "recoveries": int(reg.counter("ps/recoveries").value),
        }
    finally:
        stop_evt.set()
        try:
            if scraper is not None:
                scraper.stop()
        except Exception:
            pass
        install_scraper(None)
        install_alert_manager(None)
        if monitor is not None:
            monitor.stop()
        if tier is not None:
            try:
                tier.close()
            except Exception:
                pass
        if pub is not None:
            try:
                pub.close()
            except Exception:
                pass
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(workdir, ignore_errors=True)
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_root_cause(on_tpu):
    """Continuous-profiling chaos cell (ISSUE 20): the full anomaly →
    attribution loop with zero human-in-the-loop steps. A tiny jitted
    train program establishes a healthy step baseline and a golden
    kernel table, a `MetricsHistory` ring records every scrape sweep,
    then a `delay_ms` fault at ``exec.dispatch`` slows every step. The
    StepProfiler's MAD detector flags the straggler, the
    `ProfileTrigger` auto-captures a bounded trace and diffs it against
    the golden, and the SLO engine's anomaly-ratio page must arrive
    ALREADY annotated with >=1 named culprit kernel and a ``/history``
    window covering the anomaly. `tools/postmortem` then renders the
    bundle, and the history ring's memory estimate must stay under its
    configured cap for the whole run."""
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import faults, layers
    from paddle_tpu.observability import (AlertManager, FederatedScraper,
                                          MetricsHistory, ProfileTrigger,
                                          ScrapeTarget, SloEngine, SloSpec,
                                          install_alert_manager,
                                          install_history, install_scraper,
                                          install_trigger, record_golden)
    from paddle_tpu.observability.steps import get_step_profiler
    from paddle_tpu.tools import postmortem

    sweep_s = 0.25
    window_scale = 1.0 / 720.0   # page windows compress to ~5 s
    healthy_steps = 48           # > min_samples so the baseline is live
    delay_ms = 60.0              # ~20x a healthy CPU step: unambiguous
    history_cap_mb = 2.0

    env_keys = ["PDTPU_FLIGHT_DIR", "PDTPU_GOLDEN_DIR",
                "PDTPU_HISTORY_DIR", "PDTPU_PROFILE_ON_ANOMALY",
                "PDTPU_PROFILE_COOLDOWN_S", "PDTPU_PROFILE_MAX_CAPTURES"]
    saved_env = {k: os.environ.get(k) for k in env_keys}
    workdir = tempfile.mkdtemp(prefix="pdtpu_bench_rootcause_")
    os.environ["PDTPU_FLIGHT_DIR"] = os.path.join(workdir, "flight")
    os.environ["PDTPU_GOLDEN_DIR"] = os.path.join(workdir, "golden")
    os.environ["PDTPU_HISTORY_DIR"] = os.path.join(workdir, "history")
    os.environ["PDTPU_PROFILE_ON_ANOMALY"] = "1"
    # short cooldown: the page's enrichment may legitimately re-arm
    os.environ["PDTPU_PROFILE_COOLDOWN_S"] = "2"
    os.environ["PDTPU_PROFILE_MAX_CAPTURES"] = "4"

    steps_prof = get_step_profiler()
    steps_prof.reset()

    # enough real math (matmul + tanh) that the trace has named kernels
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data("x", [64], dtype="float32")
        h = layers.fc(x, size=64, act="tanh")
        loss = layers.reduce_mean(h * h)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    feed = {"x": np.ones((8, 64), dtype=np.float32)}
    exe = fluid.Executor(fluid.TPUPlace() if on_tpu else fluid.CPUPlace())

    scraper = trig = None
    hist_bytes_max = [0]
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)

            def run_step():
                exe.run(main_p, feed=feed, fetch_list=[loss])

            run_step()
            run_step()   # compile + warm before the golden capture
            golden = record_golden(run_step, steps=2)

            am = AlertManager(for_s=0.0, resolved_hold_s=600.0)
            install_alert_manager(am)
            events = []   # (wall_t, event) timeline from the sink
            am.add_sink(lambda ev: events.append((time.time(), ev)))

            hist = MetricsHistory(max_mb=history_cap_mb)
            install_history(hist)
            trig = ProfileTrigger(window_steps=2)
            install_trigger(trig)
            trig.attach(steps_prof, am)

            scraper = FederatedScraper([ScrapeTarget.local()],
                                       interval_s=sweep_s, timeout=0.5)
            hist.attach(scraper)
            scraper.add_sweep_listener(
                lambda doc: hist_bytes_max.__setitem__(
                    0, max(hist_bytes_max[0],
                           hist.stats()["est_bytes"])))
            engine = SloEngine(
                [SloSpec.ratio("StepAnomalyRatio", "steps/anomalies",
                               "steps/total", objective=0.99,
                               description="step straggler ratio")],
                alert_manager=am, window_scale=window_scale)
            engine.attach(scraper)
            install_scraper(scraper)
            scraper.start()

            for _ in range(healthy_steps):
                run_step()
                time.sleep(0.01)
            time.sleep(2 * sweep_s)   # healthy ratio sweeps on record

            fault_t = time.time()
            faults.install("exec.dispatch", "delay_ms", delay_ms)

            # keep stepping THROUGH the fault: the trigger's capture
            # window closes on live steps, and enrichment blocks the
            # sweep thread until the attribution exists
            def enriched_page():
                for t, ev in events:
                    if (ev["event"] == "firing"
                            and ev["severity"] == "page"
                            and (ev.get("annotations") or {}).get(
                                "culprit_kernels")):
                        return t, ev
                return None

            page = None
            deadline = time.time() + 30.0
            while time.time() < deadline and page is None:
                run_step()
                page = enriched_page()
            faults.clear()
            trig.wait_idle(10.0)
            assert page is not None, (
                f"no enriched page within 30 s; events="
                f"{[e for _, e in events]} "
                f"last_attr={trig.last_attribution()}")
            page_t, page_ev = page
            ann = page_ev["annotations"]
            culprits = ann["culprit_kernels"]
            culprit_named = bool(culprits and culprits[0].get("kernel"))
            assert culprit_named, f"no named culprit: {culprits}"
            assert ann.get("history"), (
                f"page lacks a /history window: {sorted(ann)}")
            hwin = ann["history"]
            assert hwin.get("series"), "history window carried no series"

            # a few healthy sweeps so the postmortem shows the recovery
            for _ in range(10):
                run_step()
                time.sleep(0.02)
            time.sleep(2 * sweep_s)

            report = postmortem.build_report(center_t=fault_t)
            md = postmortem.render_markdown(report)
            assert culprits[0]["kernel"] in md, (
                "postmortem does not name the culprit kernel")

            cap_bytes = hist.max_bytes
            history_under_cap = 0 < hist_bytes_max[0] <= cap_bytes
            return {
                "sweep_s": sweep_s, "window_scale": window_scale,
                "delay_ms": delay_ms, "healthy_steps": healthy_steps,
                "page_fire_after_fault_ms": round(
                    (page_t - fault_t) * 1e3, 1),
                "culprit_named": culprit_named,
                "culprit_kernels": [c.get("kernel") for c in culprits],
                "culprit_reasons": [c.get("why") for c in culprits
                                    if c.get("why")],
                "history_window_series": len(hwin["series"]),
                "history_under_cap": history_under_cap,
                "history_est_bytes_max": int(hist_bytes_max[0]),
                "history_cap_bytes": int(cap_bytes),
                "history_stats": hist.stats(),
                "golden_path": golden,
                "attribution_trigger": ann.get("attribution_trigger"),
                "postmortem_md_chars": len(md),
                "alert_events": len(events),
            }
    finally:
        faults.clear()
        try:
            if scraper is not None:
                scraper.stop()
        except Exception:
            pass
        install_scraper(None)
        install_alert_manager(None)
        install_history(None)
        install_trigger(None)
        if trig is not None:
            steps_prof.remove_listener(trig.on_record)
            steps_prof.remove_listener(trig.on_anomaly)
        steps_prof.reset()
        shutil.rmtree(workdir, ignore_errors=True)
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _roofline_diff_vs_baseline(base, rn_roofline, nmt_shapes):
    """Per-kernel roofline diff (tools/roofline.diff_tables) of this run's
    live traces vs the baseline doc's recorded tables. Sections without a
    table on BOTH sides (CPU runs, truncated baselines) are skipped and
    named in `missing` so absence reads as absence, not as 'no movement'."""
    from paddle_tpu.tools.roofline import diff_tables

    def _table(d):
        pk = (d or {}).get("per_kernel")
        return pk if isinstance(pk, dict) and "kernels" in pk else None

    bex = (base or {}).get("extra") or {}
    b_shapes = bex.get("nmt_big_shapes") or []
    pairs = {
        "resnet50": (_table(bex.get("resnet50_roofline")),
                     _table(rn_roofline)),
        "nmt_big": (_table(b_shapes[0] if b_shapes else None),
                    _table(nmt_shapes[0] if nmt_shapes else None)),
    }
    out = {"sections": {}, "missing": []}
    for name, (old, new) in pairs.items():
        if old is None or new is None:
            out["missing"].append(
                f"{name}: {'baseline' if old is None else 'fresh'}"
                " table absent")
            continue
        try:
            out["sections"][name] = diff_tables(old, new)
        except Exception as e:  # diff must not kill the bench
            out["missing"].append(f"{name}: diff failed: {str(e)[:80]}")
    return out


def _finalize(doc, gate_against=None):
    """`bench.py --finalize [BASELINE]` entry point (the merged doc arrives
    on stdin): attach the per-kernel roofline diff, print the final JSON
    line and run the regression gate. A child of its own because the
    roofline and gate tools import the package, and with it JAX."""
    extra = doc["extra"]
    # kernel-campaign sidecar: per-kernel roofline diff of this run's
    # traces vs the pre-campaign baseline doc (when it carries tables) —
    # the before/after evidence for the fused conv+BN and block-sparse
    # attention kernels lands next to BENCH_r0x, not buried in prose
    base = base_err = None
    if gate_against:
        from paddle_tpu.tools.perf_gate import load_doc
        try:
            base = load_doc(gate_against)
        except (OSError, ValueError) as e:
            base_err = str(e)
    rdiff = _roofline_diff_vs_baseline(base, extra.get("resnet50_roofline"),
                                       extra.get("nmt_big_shapes"))
    if gate_against:
        stem = os.path.splitext(os.path.basename(gate_against))[0]
        sidecar = f"ROOFLINE_DIFF_vs_{stem}.json"
        try:
            with open(sidecar, "w") as f:
                json.dump({"baseline": gate_against, "diff": rdiff}, f,
                          indent=1, sort_keys=True)
            rdiff = dict(rdiff, sidecar=sidecar)
        except OSError:
            pass
    extra["roofline_diff"] = rdiff
    print(json.dumps(doc))

    # regression gate (tools/perf_gate.py): the stated check for every
    # future BENCH_r0x round. The report goes to stderr so stdout stays
    # the single JSON line the driver parses; the exit code carries the
    # verdict (0 pass, 1 regression, 2 unusable baseline).
    if gate_against:
        from paddle_tpu.tools.perf_gate import gate
        if base is None:
            print(f"perf_gate: {base_err}", file=sys.stderr)
            return 2
        return gate(doc, base, out=sys.stderr)
    return 0


def _finalize_subprocess(doc, gate_against=None):
    """Run `_finalize` in a child, relaying its stdout (the final JSON
    line) and stderr (the gate report). Returns the child's exit code."""
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__), "--finalize"]
    if gate_against:
        cmd.append(gate_against)
    return subprocess.run(cmd, input=json.dumps(doc), text=True).returncode


def main(gate_against=None, recalibrate=False):
    """The sequencer. Imports no JAX and holds no chip: every section, and
    the finalizer, is a child process, one at a time. Exits nonzero when
    any section failed (after the JSON line is printed) or the gate says
    so."""
    doc = {"metric": "ernie_base_pretrain_tokens_per_sec_per_chip",
           "value": None, "unit": "tokens/s/chip", "vs_baseline": None,
           "extra": {}}
    extras2 = doc["extra"]
    failed = []
    for name in SECTIONS:
        res, errrec = _run_section_subprocess(
            name, extras2, recalibrate=recalibrate and name == "bert")
        if res is not None:
            extras2.update(res.pop("extra", {}))
            doc.update(res)  # headline fields (bert only)
            if name in _ERROR_KEYS:
                extras2[_ERROR_KEYS[name]] = None
            continue
        failed.append(name)
        print(f"bench: section {name} failed: {errrec['error']}",
              file=sys.stderr)
        if name in _ERROR_KEYS:
            extras2[_ERROR_KEYS[name]] = errrec["error"]
            extras2[f"{name}_flight_dump"] = errrec["flight_dump"]
            if errrec.get("plan") is not None:
                extras2[f"{name}_oom_plan"] = errrec["plan"]
        else:
            extras2[name] = {"error": errrec["error"]}
    rc = _finalize_subprocess(doc, gate_against)
    if failed:
        print(f"bench: failed sections: {', '.join(failed)}",
              file=sys.stderr)
    return rc or (1 if failed else 0)


if __name__ == "__main__":
    argv = sys.argv[1:]
    if len(argv) >= 2 and argv[0] == "--section":
        _run_section_child(argv[1], recalibrate="--recalibrate" in argv)
    elif argv and argv[0] == "--finalize":
        sys.exit(_finalize(json.load(sys.stdin),
                           argv[1] if len(argv) > 1 else None))
    else:
        gate_path = None
        if "--gate-against" in argv:
            i = argv.index("--gate-against")
            if i + 1 >= len(argv):
                print("bench.py: --gate-against needs a baseline path",
                      file=sys.stderr)
                sys.exit(2)
            gate_path = argv[i + 1]
        sys.exit(main(gate_against=gate_path,
                      recalibrate="--recalibrate" in argv))
