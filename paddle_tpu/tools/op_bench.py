"""Per-op micro-benchmark harness.

Reference analog: ``paddle/fluid/operators/benchmark/op_tester.cc`` — a
config-driven runner that builds one op, feeds synthetic tensors, and
reports per-op latency (op_tester.cc:1; config format op_tester_config.cc).
BASELINE.md requires this harness to exist "from day one" since all speedup
claims are measured, not quoted.

TPU-native redesign: the op executes through the same Program→Executor→XLA
path as production (so the measurement includes our lowering, XLA fusion,
and dispatch), with an explicit compile warmup so steady-state latency is
reported separately from compile time.

CLI::

    python -m paddle_tpu.tools.op_bench --op matmul \
        --input X=256x256 --input Y=256x256 --repeat 200
    python -m paddle_tpu.tools.op_bench --config bench_ops.json

Config file: a JSON list of {"op", "inputs": {slot: {"shape", "dtype"}},
"attrs", "outputs", "repeat"}. Output: one JSON line per config with
{op, mean_us, min_us, p50_us, compile_ms, repeat}.
"""
from __future__ import annotations

import argparse
import json
import time
import zlib
from typing import Dict, List, Optional

import numpy as np


def _make_input(shape, dtype, seed=0):
    rng = np.random.RandomState(seed)
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.randint(0, 8, size=shape).astype(dtype)
    return rng.rand(*shape).astype(dtype)


def bench_op(op_type: str, inputs: Dict[str, Dict], attrs: Optional[dict] = None,
             outputs: Optional[Dict[str, int]] = None, repeat: int = 100,
             warmup: int = 2) -> dict:
    """Build a one-op program, execute through the real Executor, time it.

    inputs: slot -> {"shape": [..], "dtype": "float32"} (or a list of such
    for multi-value slots). outputs: slot -> count (default {"Out": 1}).
    """
    import paddle_tpu as fluid

    attrs = attrs or {}
    outputs = outputs or {"Out": 1}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        block = main.global_block()
        in_map, feed = {}, {}
        for slot, specs in inputs.items():
            specs = specs if isinstance(specs, list) else [specs]
            names = []
            for i, sp in enumerate(specs):
                a = _make_input(sp["shape"], sp.get("dtype", "float32"),
                                seed=(zlib.crc32(slot.encode()) + i) % 2 ** 31)
                name = f"{slot.lower()}_{i}"
                block.create_var(name=name, shape=a.shape, dtype=str(a.dtype),
                                 is_data=True)
                feed[name] = a
                names.append(name)
            in_map[slot] = names
        out_map = {}
        for slot, n in outputs.items():
            out_map[slot] = [f"out_{slot.lower()}_{i}" for i in range(n)]
            for nm in out_map[slot]:
                block.create_var(name=nm, dtype="float32")
        block.append_op(op_type, in_map, out_map, attrs)
        fetch = [nm for slot in sorted(out_map) for nm in out_map[slot]]

        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            t0 = time.perf_counter()
            exe.run(main, feed=feed, fetch_list=fetch)
            compile_ms = (time.perf_counter() - t0) * 1e3
            for _ in range(warmup):
                exe.run(main, feed=feed, fetch_list=fetch)
            times = []
            for _ in range(repeat):
                t0 = time.perf_counter()
                res = exe.run(main, feed=feed, fetch_list=fetch,
                              return_numpy=False)
                np.asarray(res[0])  # sync
                times.append(time.perf_counter() - t0)
    times = np.array(times) * 1e6
    return {"op": op_type,
            "mean_us": round(float(times.mean()), 2),
            "min_us": round(float(times.min()), 2),
            "p50_us": round(float(np.percentile(times, 50)), 2),
            "compile_ms": round(compile_ms, 2),
            "repeat": repeat}


def _parse_input_flag(s: str):
    # "X=256x256" or "X=256x256:int64"
    slot, rest = s.split("=", 1)
    parts = rest.split(":")
    shape = [int(d) for d in parts[0].split("x")]
    dtype = parts[1] if len(parts) > 1 else "float32"
    return slot, {"shape": shape, "dtype": dtype}


def bench_dygraph_mlp(steps: int = 50, batch: int = 64, width: int = 256,
                      depth: int = 4):
    """Dygraph transformer-style MLP train-step micro-bench (VERDICT r3
    #9): linear → layer_norm → gelu blocks, the realistic dygraph op mix
    (multi-primitive ops are where per-op jit caching pays — a bare
    single-primitive relu MLP measures launch count, not fusion). Eager
    per-op jit cache (ops/eager.py _prepare — the PreparedOp analog,
    imperative/prepared_operator.h) vs raw per-primitive dispatch
    (PDTPU_EAGER_JIT=0). The two arms run as INTERLEAVED 10-step
    segments and report per-arm medians, so that drift over the run hits
    both arms alike. Returns {cached_ms, uncached_ms, speedup}."""
    import os
    import statistics
    import time

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import dygraph
    from paddle_tpu.ops import eager as _eager

    rng = np.random.RandomState(0)
    X = rng.rand(batch, width).astype("float32")
    Y = rng.rand(batch, 1).astype("float32")
    seg = 10
    n_seg = max(2, steps // seg)

    old = os.environ.get("PDTPU_EAGER_JIT")
    os.environ.pop("PDTPU_EAGER_JIT", None)
    try:
        with dygraph.guard(seed=7):
            layers_ = [dygraph.nn.Linear(width, width)
                       for i in range(depth)] + [dygraph.nn.Linear(width, 1)]
            lns = [dygraph.nn.LayerNorm(width) for _ in range(depth)]
            opt = fluid.optimizer.SGD(0.01)
            xv = dygraph.to_variable(X)
            yv = dygraph.to_variable(Y)
            from paddle_tpu.dygraph.tracer import trace_op
            params = [q for ly in layers_ + lns for q in ly.parameters()]

            def step():
                h = xv
                for i, ly in enumerate(layers_[:-1]):
                    h = ly(h)
                    h = lns[i](h)
                    h = trace_op("gelu", {"X": [h]}, {})["Out"][0]
                h = layers_[-1](h)
                diff = trace_op("elementwise_sub", {"X": [h], "Y": [yv]},
                                {"axis": -1})["Out"][0]
                sq = trace_op("square", {"X": [diff]}, {})["Out"][0]
                loss = trace_op("mean", {"X": [sq]}, {})["Out"][0]
                loss.backward()
                opt.minimize(loss, parameter_list=params)
                for ly in layers_ + lns:
                    ly.clear_gradients()
                return loss

            def segment(cached: bool):
                if cached:
                    os.environ.pop("PDTPU_EAGER_JIT", None)
                else:
                    os.environ["PDTPU_EAGER_JIT"] = "0"
                step()  # warmup/compile for this arm
                t0 = time.time()
                for _ in range(seg):
                    loss = step()
                np.asarray(loss.value)
                return (time.time() - t0) / seg * 1e3

            cached_t, uncached_t = [], []
            for _ in range(n_seg):
                cached_t.append(segment(True))
                uncached_t.append(segment(False))
    finally:
        if old is not None:
            os.environ["PDTPU_EAGER_JIT"] = old
        else:
            os.environ.pop("PDTPU_EAGER_JIT", None)
    def _iqr(xs):
        qs = statistics.quantiles(xs, n=4) if len(xs) >= 2 else [0, 0, 0]
        return round(qs[2] - qs[0], 3)

    cached = statistics.median(cached_t)
    uncached = statistics.median(uncached_t)
    return {"bench": "dygraph_mlp_step", "steps": steps,
            "cached_ms": round(cached, 3), "uncached_ms": round(uncached, 3),
            "cached_iqr_ms": _iqr(cached_t),
            "uncached_iqr_ms": _iqr(uncached_t),
            "n_segments": n_seg,
            "speedup": round(uncached / cached, 2)}


def _interleaved_ab(arms: Dict[str, callable], n_seg: int = 5,
                    seg_iters: int = 5) -> dict:
    """Shared A/B protocol: all arms pre-compiled, then INTERLEAVED
    timed segments with per-arm medians + IQR — back-to-back A/B runs
    are meaningless under drifting dispatch latency."""
    import statistics

    import jax

    for f in arms.values():  # compile off the clock
        np.asarray(jax.tree_util.tree_leaves(f())[0]).ravel()[:1]

    def _seg(f):
        t0 = time.perf_counter()
        for _ in range(seg_iters):
            o = f()
        np.asarray(jax.tree_util.tree_leaves(o)[0]).ravel()[:1]
        return (time.perf_counter() - t0) / seg_iters * 1e3

    times = {k: [] for k in arms}
    for _ in range(n_seg):
        for k, f in arms.items():
            times[k].append(_seg(f))

    def _iqr(xs):
        qs = statistics.quantiles(xs, n=4) if len(xs) >= 2 else [0, 0, 0]
        return round(qs[2] - qs[0], 3)

    return {k: {"median_ms": round(statistics.median(v), 3),
                "iqr_ms": _iqr(v), "n_segments": n_seg}
            for k, v in times.items()}


def bench_fused_conv_bn(batch: int = 8, ci: int = 64, co: int = 256,
                        hw: int = 32, stride: int = 1, n_seg: int = 5):
    """Standalone A/B cell for the fused 1×1-conv+BN(+relu+residual)
    Pallas kernel vs the exact XLA composition it replaces
    (ops/pallas_kernels/fused_bn.py): fwd and fwd+bwd arms, interleaved
    segments. Needs a TPU; under `fused_bn.FORCE_PALLAS_INTERPRET` (tests)
    the Pallas arm runs the interpreter — parity, not speed."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import fused_bn

    on_tpu = fused_bn._on_tpu()
    if not (on_tpu or fused_bn.FORCE_PALLAS_INTERPRET):
        raise RuntimeError(
            f"bench_fused_conv_bn needs a TPU, found backend "
            f"{jax.default_backend()!r}")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, ci, hw, hw), jnp.float32)
    w = jnp.asarray(rng.randn(co, ci, 1, 1) * 0.1, jnp.float32)
    scale = jnp.asarray(rng.rand(co) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(co) * 0.1, jnp.float32)
    eps = 1e-5

    def fused(x, w, scale, bias):
        y, _, _ = fused_bn.fused_conv_bn_act(x, w, scale, bias, eps, "relu",
                                             stride, False, None)
        return y

    def unfused(x, w, scale, bias):
        y, _, _ = fused_bn.conv_bn_xla(x, w, scale, bias, eps, "relu",
                                       stride, None)
        return y

    f_p = jax.jit(fused)
    f_x = jax.jit(unfused)
    g_p = jax.jit(jax.grad(lambda *a: jnp.sum(fused(*a) ** 2), (0, 1, 2, 3)))
    g_x = jax.jit(jax.grad(lambda *a: jnp.sum(unfused(*a) ** 2),
                           (0, 1, 2, 3)))
    args = (x, w, scale, bias)
    res = _interleaved_ab({
        "pallas_fwd": lambda: f_p(*args), "xla_fwd": lambda: f_x(*args),
        "pallas_bwd": lambda: g_p(*args), "xla_bwd": lambda: g_x(*args),
    }, n_seg=n_seg)
    return {"bench": "fused_conv_bn",
            "shape": [batch, ci, hw, hw], "co": co, "stride": stride,
            "interpret": not on_tpu,
            "arms": res,
            "fwd_speedup": round(res["xla_fwd"]["median_ms"]
                                 / res["pallas_fwd"]["median_ms"], 2),
            "bwd_speedup": round(res["xla_bwd"]["median_ms"]
                                 / res["pallas_bwd"]["median_ms"], 2)}


def bench_block_sparse_attn(batch: int = 2, t: int = 512, hidden: int = 256,
                            num_heads: int = 4, avg_sent: int = 48,
                            n_seg: int = 5):
    """Standalone A/B cell for block-sparse packed-segment attention vs
    the dense-additive-mask flash path on the same packed batch
    (ops/pallas_kernels/flash_attention.py): fwd and fwd+bwd arms,
    interleaved segments. The dense arm pays every K block; the sparse
    arm skips fully-masked ones, so the gap scales with pad/pack waste.
    Needs a TPU; under the module's FORCE_PALLAS_INTERPRET (tests) the
    Pallas arms run the interpreter."""
    import importlib

    import jax
    import jax.numpy as jnp

    _fa = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")

    on_tpu = _fa._on_tpu()
    if not (on_tpu or _fa.FORCE_PALLAS_INTERPRET):
        raise RuntimeError(
            f"bench_block_sparse_attn needs a TPU, found backend "
            f"{jax.default_backend()!r}")

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(batch, t, hidden), jnp.float32)
    k = jnp.asarray(rng.randn(batch, t, hidden), jnp.float32)
    v = jnp.asarray(rng.randn(batch, t, hidden), jnp.float32)
    seg_np = np.zeros((batch, t), "int32")
    for b in range(batch):
        p, sid = 0, 1
        while p < t - 4:
            ln = min(int(rng.randint(avg_sent // 2, avg_sent * 2)), t - p)
            seg_np[b, p:p + ln] = sid
            p += ln
            sid += 1
            if rng.rand() < 0.3:  # leave a pad tail on some rows
                break
    seg = jnp.asarray(seg_np)
    neg = jnp.where((seg[:, :, None] == seg[:, None, :])
                    & (seg[:, :, None] > 0), 0.0, -1e30).astype(jnp.float32)

    def sparse(q, k, v):
        return _fa.flash_attention_packed_sparse(q, k, v, num_heads, seg,
                                                 seg)

    def dense(q, k, v):
        # the dense [B, 1, Tq, Tk] additive segment mask through the
        # 4D bias path — what the packed NMT model fed before the
        # descriptor existed
        d = hidden // num_heads

        def heads(x):
            return x.reshape(batch, t, num_heads, d).transpose(0, 2, 1, 3)

        o = _fa.flash_attention(heads(q), heads(k), heads(v),
                                bias=neg[:, None])
        return o.transpose(0, 2, 1, 3).reshape(batch, t, hidden)

    f_s = jax.jit(sparse)
    f_d = jax.jit(dense)
    g_s = jax.jit(jax.grad(lambda *a: jnp.sum(sparse(*a) ** 2), (0, 1, 2)))
    g_d = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), (0, 1, 2)))
    args = (q, k, v)
    res = _interleaved_ab({
        "sparse_fwd": lambda: f_s(*args), "dense_fwd": lambda: f_d(*args),
        "sparse_bwd": lambda: g_s(*args), "dense_bwd": lambda: g_d(*args),
    }, n_seg=n_seg)
    fill = float((seg_np > 0).mean())
    return {"bench": "block_sparse_attn",
            "shape": [batch, t, hidden], "num_heads": num_heads,
            "fill_rate": round(fill, 4),
            "interpret": not on_tpu,
            "arms": res,
            "fwd_speedup": round(res["dense_fwd"]["median_ms"]
                                 / res["sparse_fwd"]["median_ms"], 2),
            "bwd_speedup": round(res["dense_bwd"]["median_ms"]
                                 / res["sparse_bwd"]["median_ms"], 2)}


def main(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dygraph", action="store_true",
                    help="run the dygraph MLP step bench (eager jit cache "
                         "on vs off)")
    ap.add_argument("--fused-conv-bn", action="store_true",
                    help="A/B the fused conv+BN Pallas kernel vs its XLA "
                         "composition")
    ap.add_argument("--block-sparse-attn", action="store_true",
                    help="A/B block-sparse packed-segment attention vs the "
                         "dense-mask flash path")
    ap.add_argument("--op")
    ap.add_argument("--input", action="append", default=[],
                    help="SLOT=shape[:dtype], e.g. X=256x256:float32")
    ap.add_argument("--attrs", default="{}", help="JSON attr dict")
    ap.add_argument("--out", action="append", default=[],
                    help="output slot[:count], default Out:1")
    ap.add_argument("--repeat", type=int, default=100)
    ap.add_argument("--config", help="JSON list of bench specs")
    args = ap.parse_args(argv)

    specs = []
    if args.config:
        with open(args.config) as f:
            specs = json.load(f)
    if args.op:
        inputs = {}
        for s in args.input:
            slot, sp = _parse_input_flag(s)
            inputs.setdefault(slot, []).append(sp)
        outputs = {}
        for o in args.out:
            slot, _, n = o.partition(":")
            outputs[slot] = int(n or 1)
        specs.append({"op": args.op, "inputs": inputs,
                      "attrs": json.loads(args.attrs),
                      "outputs": outputs or None, "repeat": args.repeat})
    ran_cell = False
    if args.dygraph:
        print(json.dumps(bench_dygraph_mlp()))
        ran_cell = True
    if args.fused_conv_bn:
        print(json.dumps(bench_fused_conv_bn()))
        ran_cell = True
    if args.block_sparse_attn:
        print(json.dumps(bench_block_sparse_attn()))
        ran_cell = True
    if ran_cell and not specs:
        return
    if not specs:
        ap.error("need --op or --config")

    for sp in specs:
        res = bench_op(sp["op"], sp["inputs"], sp.get("attrs"),
                       sp.get("outputs"), sp.get("repeat", 100))
        print(json.dumps(res))


if __name__ == "__main__":
    main()
