#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the only one that touches JAX, drives the main path once through
the entry points a user calls and checks what comes out. It fails (exit code
nonzero, no result line, the failing phase named on the last line) when JAX
finds no TPU, when the device kind has no published peaks, or when any phase
fails. On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python chip_smoke.py            # one chip (the default, and the contract)
    python chip_smoke.py --chips 4  # data-parallel ERNIE over a four-chip host

Phases, one chip:

  device   backend is "tpu" and `device_kind` is in calibrate.PEAKS; versions
           and the compile-cache directory in effect are printed
  trainer  ERNIE/BERT-base at full width and depth (b64 x 512, bf16 AMP Adam,
           dropout 0.1, flash attention): startup + ten Executor.run steps on
           one fixed batch; losses finite and falling, results resident on the
           TPU, a Mosaic call in the compiled step, no compile after step two
  kernels  every Pallas kernel a model can reach, compiled (never the
           interpreter), run and compared on the chip with an XLA reference at
           the shapes the models use
  deepfm   three DeepFM steps at the benchmark's configuration (33.5M-row
           packed table, batch 4096, exact Adagrad) on its default path
  looped   three steps of a looped decoder (models/ouro.py) at Ouro-2.6B's
           widths, two layers run twice over shared weights, b1 x 1024, bf16
           AMP Adam: one parameter a layer's matrix, the exit shares sum to 1
  lfm2     three steps of models/lfm2.py at LFM2-24B-A2B's widths, the dense
           layer and one period (conv + dense MLP; attention, conv, conv,
           conv with 8 of 64 gated experts held), b1 x 1024, bf16 AMP Adam:
           the head reads the embedding's table (one parameter), every held
           pair is multiplied
  joyai    three steps of models/joyai_flash.py at JoyAI-LLM-Flash's widths:
           the dense layer, one expert layer (16 of 256 gated experts held,
           a shared expert) and the multi-token-prediction module, latent
           attention with 192-wide keys and 128-wide values, b1 x 1024, bf16
           AMP Adam: the head matrix and the table are one parameter each
           with two readers, both loss terms fall

  laguna   three steps of models/laguna.py at Laguna-XS.2's widths: the
           dense layer at 48 heads with YaRN on half of each head, and one
           window layer (window 512) at 64 heads with 32 of 256 gated
           experts held beside a shared expert, the per-head output gate in
           both, b1 x 1024, bf16 AMP Adam: the loss falls, every held pair
           is counted
  kda      one Kimi Delta Attention layer's call of the chunked gated delta
           rule (ops/linear_attn_ops.py) at the benchmark cell's shape, b2 x
           T8192, 32 heads of 128, bf16 operands, the l2 normalisation and
           the decay gate inside, a decay floor past float32's e^-88: the op
           takes its Pallas kernels (ops/pallas_kernels/kda_chunk.py), and
           its result and all seven gradients (q, k, v, the gate's values,
           beta, A_log, dt_bias) stand against the token-by-token recurrence
           in float32

`--chips 4` runs `device` and then `dp4`: the same ERNIE program under
CompiledProgram.with_data_parallel at 64 per chip, checking the four-way feed
split, the state shardings, the memory spread, and five dp4 losses against
five one-chip losses.

Timings printed here (compile seconds, step ms) are set-up facts from one
run, not benchmark numbers. Details too long for stdout go to
``chiprun_out/chip_smoke_chips<N>.json``.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.metadata
import json
import os
import sys
import time
import traceback
from typing import Callable, NamedTuple, Optional

import numpy as np

OUT_DIR = "chiprun_out"

# ERNIE/BERT-base at the shape of the benchmark's cell ernie_base.seq512
ERNIE_LAYERS = 12
ERNIE_BATCH, ERNIE_SEQ = 64, 512
ERNIE_STEPS = 10
# DeepFM at the benchmark's configuration deepfm_criteo
DEEPFM_VOCAB, DEEPFM_BATCH, DEEPFM_STEPS = 33_554_432, 4096, 3
# the looped decoder: depth, passes and batch small enough to add under 30 s
LOOPED_LAYERS, LOOPED_PASSES, LOOPED_SEQ, LOOPED_STEPS = 2, 2, 1024, 3
# the hybrid decoder: the dense layer and one period of the layer pattern
LFM2_LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]
LFM2_SEQ, LFM2_STEPS = 1024, 3
# dp4: per-chip batch of the sharded run, and the dropout-free equality run
DP4_PER_CHIP, DP4_EQ_BATCH, DP4_EQ_STEPS, DP4_EQ_RTOL = 64, 64, 5, 1e-2

# jax.monitoring event fired around every backend compile (cache hit or miss)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _CompileCounter:
    """Counts backend compiles through jax.monitoring (every jit in the
    process, not only the executor's own cache misses)."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


def _registry_value(name: str) -> float:
    from paddle_tpu.observability import get_registry
    return float(get_registry().snapshot().get(name, 0.0))


def _platforms(arr) -> set:
    return {d.platform for d in arr.devices()}


def _mem_stat(dev, name: str) -> int:
    return int(dev.memory_stats()[name])


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(args):
    import jax

    backend = jax.default_backend()
    _require(backend == "tpu",
             f"chip_smoke needs a TPU: jax.default_backend() is {backend!r}")
    devs = jax.devices()
    from paddle_tpu.observability import calibrate

    kind = devs[0].device_kind
    _require(kind in calibrate.PEAKS,
             f"device kind {kind!r} has no entry in calibrate.PEAKS "
             f"(known: {sorted(calibrate.PEAKS)})")
    _require(len(devs) >= args.chips,
             f"--chips {args.chips} needs {args.chips} devices, "
             f"jax.devices() has {len(devs)}")
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    out = {
        "platform": devs[0].platform, "kind": kind, "count": len(devs),
        "versions": versions,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile_cache_entries_at_start": int(_registry_value(
            "executor/compile_cache_entries_at_start")),
    }
    print(f"device: platform={out['platform']} kind={kind!r} "
          f"count={out['count']} "
          + " ".join(f"{k}={v}" for k, v in versions.items()))
    print(f"device: compile cache {out['compile_cache_dir']} held "
          f"{out['compile_cache_entries_at_start']} entries at start")
    return out


# ---------------------------------------------------------------------------
# trainer: ERNIE/BERT-base through Program -> Executor
# ---------------------------------------------------------------------------

def ernie_program(batch: int, seq: int, dropout: float):
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import bert

    cfg = bert.BertConfig(num_layers=ERNIE_LAYERS, hidden_size=768,
                          num_heads=12, ffn_size=3072, vocab_size=30522,
                          hidden_dropout=dropout, attn_dropout=dropout)

    def opt():
        return mp.decorate(fluid.optimizer.Adam(1e-4), dtype="bfloat16",
                           use_dynamic_loss_scaling=False)

    main, startup, _, loss = bert.build_pretrain_program(
        cfg, batch, seq, optimizer_factory=opt)
    return cfg, main, startup, loss


def ernie_feed(cfg, batch: int, seq: int) -> dict:
    rng = np.random.RandomState(0)
    return {
        "src_ids": rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"),
        "pos_ids": np.tile(np.arange(seq), (batch, 1)).astype("int32"),
        "sent_ids": np.zeros((batch, seq), dtype="int32"),
        "input_mask": np.ones((batch, seq), dtype="float32"),
        "mlm_labels": rng.randint(
            0, cfg.vocab_size, (batch, seq, 1)).astype("int32"),
    }


def _compiled_step(exe, program):
    """The executable `exe` compiled for `program`. Reads the executor's
    cache because nothing public hands it out; the AUTO-layout AOT step must
    exist on a TPU (its absence would mean the executor quietly fell back to
    the plain jit)."""
    steps = [fn for key, fn in exe._cache.items() if key[0] == id(program)]
    _require(len(steps) == 1,
             f"expected one compiled step for the program, found {len(steps)}")
    _require(steps[0]._compiled is not None,
             "the executor holds no AUTO-layout executable for the step")
    return steps[0]._compiled


def _hbm_by_xla(compiled) -> dict:
    """XLA's own account of what the executable needs in HBM. On this
    runtime memory_stats()' peak_bytes_in_use counts live arrays only, not
    the program's temporaries, so it understates a training step several
    times over."""
    ma = compiled.memory_analysis()
    return {"argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "live_bytes": int(ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes
                              + ma.temp_size_in_bytes)}


def phase_trainer(args):
    import jax

    import paddle_tpu as fluid

    compiles = _CompileCounter()
    cfg, main, startup, loss = ernie_program(ERNIE_BATCH, ERNIE_SEQ, 0.1)
    feed = ernie_feed(cfg, ERNIE_BATCH, ERNIE_SEQ)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        startup_s = time.perf_counter() - t0

        losses, first_step_s, steady_t0 = [], None, None
        compiles_after_2 = misses_after_2 = None
        for step in range(ERNIE_STEPS):
            t0 = time.perf_counter()
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                            return_numpy=False)
            if step == 0:
                lv.block_until_ready()
                first_step_s = time.perf_counter() - t0
                _require(compiles.count > 0,
                         f"no {_COMPILE_EVENT} event seen during the first "
                         f"step: the compile counter is blind")
            if step == 1:
                lv.block_until_ready()
                compiles_after_2 = compiles.count
                misses_after_2 = _registry_value("executor/cache_misses")
                steady_t0 = time.perf_counter()
            losses.append(lv)
        losses[-1].block_until_ready()
        steady_ms = ((time.perf_counter() - steady_t0)
                     / (ERNIE_STEPS - 2) * 1e3)

        _require(_platforms(losses[-1]) == {"tpu"},
                 f"fetched loss lives on {_platforms(losses[-1])}, not tpu")
        param = scope.find_var("word_embedding")
        _require(param is not None and _platforms(param) == {"tpu"},
                 "parameter 'word_embedding' is not resident on the tpu")
        vals = [float(np.asarray(v)) for v in losses]
        _require(all(np.isfinite(vals)), f"non-finite loss in {vals}")
        _require(vals[-1] < vals[0],
                 f"loss did not fall over {ERNIE_STEPS} steps: {vals}")
        late = compiles.count - compiles_after_2
        late_misses = _registry_value("executor/cache_misses") - misses_after_2
        _require(late == 0 and late_misses == 0,
                 f"{late} backend compiles / {late_misses} executor cache "
                 f"misses after step two")
        compiled = _compiled_step(exe, main)
        n_mosaic = compiled.as_text().count("tpu_custom_call")
        _require(n_mosaic > 0,
                 "no tpu_custom_call in the compiled ERNIE step: attention "
                 "did not take the Pallas kernel")
        hbm = _hbm_by_xla(compiled)
        peak = _mem_stat(jax.devices()[0], "peak_bytes_in_use")
    out = {
        "config": {"layers": ERNIE_LAYERS, "batch": ERNIE_BATCH,
                   "seq": ERNIE_SEQ, "dropout": 0.1},
        "losses": [round(v, 4) for v in vals],
        "mosaic_calls_in_step": n_mosaic,
        "compiles_total": compiles.count,
        "compiles_after_step_two": late,
        "peak_bytes_in_use": peak,
        "hbm_by_xla_memory_analysis": hbm,
        "setup": {"startup_s": round(startup_s, 2),
                  "compile_plus_first_step_s": round(first_step_s, 2),
                  "backend_compile_s": round(compiles.seconds, 2),
                  "step_ms_one_run": round(steady_ms, 1)},
    }
    print(f"trainer: ERNIE-base L{ERNIE_LAYERS} b{ERNIE_BATCH}x{ERNIE_SEQ} "
          f"loss {vals[0]:.4f} -> {vals[-1]:.4f} over {ERNIE_STEPS} steps, "
          f"{n_mosaic} Mosaic calls in the step, {late} compiles after step "
          f"two")
    print(f"trainer: HBM the step needs by XLA's memory analysis "
          f"{hbm['live_bytes'] / 2**30:.2f} GiB (arguments "
          f"{hbm['argument_bytes'] / 2**30:.2f} + temporaries "
          f"{hbm['temp_bytes'] / 2**30:.2f}); memory_stats "
          f"peak_bytes_in_use {peak / 2**30:.2f} GiB (live arrays only)")
    print(f"trainer: set-up facts (one run, not a benchmark): startup "
          f"{startup_s:.1f} s, compile+first step {first_step_s:.1f} s, "
          f"then {steady_ms:.0f} ms/step")
    del losses, param, scope, exe, compiled
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# kernels: each Pallas entry point against an XLA reference, on the chip
# ---------------------------------------------------------------------------

class KernelCase(NamedTuple):
    """`kernel(*args)` and `reference(*args)` return the same tuple of
    arrays (outputs, then gradients); `make_args(rng)` draws the inputs.
    `tol` bounds ||kernel - reference|| / ||reference|| (Frobenius) per
    array: a max-norm would be set by the few elements whose relu mask
    flips when two bf16 roundings of the same value straddle zero.
    `mosaic_calls`, where given, is how many Mosaic calls forward and
    backward lower to."""

    name: str
    make_args: Callable
    kernel: Callable
    reference: Callable
    tol: float
    mosaic_calls: Optional[int] = None


def _fa():
    # the package re-exports the flash_attention *function* under the same
    # name, shadowing the submodule
    return importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")


def _with_grads(f, n_diff: int):
    """(args) -> (out, d/d(args[:n_diff]) of sum(out * w)) with a fixed
    pseudo-random cotangent w, so forward and backward are both compared."""
    import jax
    import jax.numpy as jnp

    def run(*args):
        def scalar(*diff):
            out = f(*diff, *args[n_diff:])
            w = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
                out.shape)
            return jnp.sum(out.astype(jnp.float32) * w), out
        grads, out = jax.grad(scalar, argnums=tuple(range(n_diff)),
                              has_aux=True)(*args[:n_diff])
        return (out, *grads)
    return run


def _attention_reference(q, k, v, nh, mask=None, bias=None):
    """Plain softmax attention on packed [B, T, H] tensors in f32 with exact
    matmuls. `mask` is a bool [B, 1|nh, Tq, Tk] (True = visible); rows with
    nothing visible return 0, as the kernels do."""
    import jax
    import jax.numpy as jnp

    b, tq, h = q.shape
    d = h // nh
    hp = jax.lax.Precision.HIGHEST

    def heads(x):
        return x.astype(jnp.float32).reshape(
            b, x.shape[1], nh, d).transpose(0, 2, 1, 3)

    s = jnp.einsum("bhqd,bhkd->bhqk", heads(q), heads(k),
                   precision=hp) / np.sqrt(d)
    if bias is not None:
        s = s + bias[:, None].astype(jnp.float32)      # [B,1,1,Tk]
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, heads(v), precision=hp)
    return o.transpose(0, 2, 1, 3).reshape(b, tq, h).astype(q.dtype)


def _causal(tq, tk):
    return (np.arange(tq)[:, None] >= np.arange(tk)[None, :])[None, None]


def _qkv(rng, b, tq, tk, h, dtype="bfloat16"):
    import jax.numpy as jnp
    return tuple(jnp.asarray(rng.standard_normal((b, t, h)) * 0.5, dtype)
                 for t in (tq, tk, tk))


def _segments(rng, b, t, avg):
    """Packed segment-id rows as reader.pack_by_tokens lays them out:
    1-based ascending ids, 0 = pad tail on some rows."""
    seg = np.zeros((b, t), "int32")
    for r in range(b):
        p, sid = 0, 1
        while p < t:
            ln = min(int(rng.randint(avg // 2, avg * 2)), t - p)
            seg[r, p:p + ln] = sid
            p, sid = p + ln, sid + 1
            if p > t // 2 and rng.rand() < 0.25:
                break
    return seg


def _dense_case(name, b, t, nh, causal, with_bias):
    import jax.numpy as jnp
    h = nh * 64

    def make_args(rng):
        args = _qkv(rng, b, t, t, h)
        if with_bias:
            # the BERT additive mask: 0 = keep, -10000 = pad (last eighth)
            keep = np.ones((b, 1, t), "float32")
            keep[b // 2:, :, t - t // 8:] = 0.0
            args += (jnp.asarray((keep - 1.0) * 10000.0),)
        return args

    def kernel(q, k, v, *bias):
        return _fa().flash_attention_packed(
            q, k, v, nh, bias=bias[0] if bias else None, causal=causal)

    def reference(q, k, v, *bias):
        return _attention_reference(
            q, k, v, nh, mask=_causal(t, t) if causal else None,
            bias=bias[0] if bias else None)

    return KernelCase(name, make_args, _with_grads(kernel, 3),
                      _with_grads(reference, 3), 2e-2)


def _causal_reference_by_head(q, k, v, nh, nkv, window=None):
    """Causal softmax attention on packed [B, T, H] tensors, a (batch, head)
    at a time in f32 with exact matmuls, each head's [T, T] scores made
    again in the backward pass: at T 8,192 all heads' scores at once are
    17 GB. Query head h reads key/value head h // (nh / nkv); v's head size
    is its own. Under a `window` query i sees keys i - window < j <= i."""
    import jax
    import jax.numpy as jnp

    b, t, h = q.shape
    d, dv = h // nh, v.shape[2] // nkv
    hp = jax.lax.Precision.HIGHEST

    def heads(x, n):
        x = x.astype(jnp.float32).reshape(b, t, n, -1).transpose(0, 2, 1, 3)
        return jnp.repeat(x, nh // n, axis=1).reshape(b * nh, t, -1)

    @jax.checkpoint
    def one(qkv):
        qh, kh, vh = qkv
        s = jnp.dot(qh, kh.T, precision=hp) / np.sqrt(d)
        visible = _causal(t, t)[0, 0]
        if window is not None:
            pos = jnp.arange(t)
            visible = visible & (pos[:, None] - pos[None, :] < window)
        s = jnp.where(visible, s, -1e30)
        return jnp.dot(jax.nn.softmax(s, axis=-1), vh, precision=hp)

    o = jax.lax.map(one, (heads(q, nh), heads(k, nkv), heads(v, nkv)))
    return o.reshape(b, nh, t, dv).transpose(0, 2, 1, 3).reshape(
        b, t, nh * dv).astype(q.dtype)


def _blocked_causal_case(name, b, t, nh, nkv, d, dv=None, window=None):
    """A benchmark cell's attention call: causal, blocked (T over one block),
    `nh` query heads of `d` on `nkv` key/value heads, the values `dv` wide
    (default: `d`), under a sliding `window` where given. Two Mosaic calls:
    the forward, and one backward kernel for all three gradients."""
    def make_args(rng):
        import jax.numpy as jnp
        return tuple(jnp.asarray(rng.standard_normal((b, t, width)) * 0.5,
                                 "bfloat16")
                     for width in (nh * d, nkv * d, nkv * (dv or d)))

    def kernel(q, k, v):
        return _fa().flash_attention_packed(q, k, v, nh, causal=True,
                                            num_kv_heads=nkv, window=window)

    def reference(q, k, v):
        return _causal_reference_by_head(q, k, v, nh, nkv, window)

    return KernelCase(name, make_args, _with_grads(kernel, 3),
                      _with_grads(reference, 3), 2e-2, mosaic_calls=2)


def _sparse_case(name, b, tq, tk, nh, causal):
    import jax.numpy as jnp
    h = nh * 64

    def make_args(rng):
        k_seg = _segments(rng, b, tk, 48)
        if tq == tk:
            q_seg = k_seg
        else:
            # cross attention: every query segment id exists on the key side
            q_seg = np.minimum(_segments(rng, b, tq, 48),
                               k_seg.max(axis=1, keepdims=True))
        return _qkv(rng, b, tq, tk, h) + (jnp.asarray(q_seg),
                                          jnp.asarray(k_seg))

    def kernel(q, k, v, q_seg, k_seg):
        return _fa().flash_attention_packed_sparse(
            q, k, v, nh, q_seg, k_seg, causal=causal)

    def reference(q, k, v, q_seg, k_seg):
        mask = ((q_seg[:, :, None] == k_seg[:, None, :])
                & (q_seg[:, :, None] > 0))[:, None]
        if causal:
            mask = mask & _causal(tq, tk)
        return _attention_reference(q, k, v, nh, mask=mask)

    return KernelCase(name, make_args, _with_grads(kernel, 3),
                      _with_grads(reference, 3), 2e-2)


def _bn_args(rng, n, c, hw, co=None, residual=False):
    import jax.numpy as jnp
    cc = co or c
    args = [jnp.asarray(rng.standard_normal((n, c, hw, hw)), jnp.bfloat16)]
    if co is not None:
        args.append(jnp.asarray(
            rng.standard_normal((co, c, 1, 1)) / np.sqrt(c), jnp.bfloat16))
    args += [jnp.asarray(rng.rand(cc) + 0.5, jnp.float32),
             jnp.asarray(rng.standard_normal(cc) * 0.1, jnp.float32)]
    if residual:
        args.append(jnp.asarray(rng.standard_normal((n, cc, hw, hw)),
                                jnp.bfloat16))
    return tuple(args)


def _bn_case(name, n, c, hw):
    """fused_bn_act (+relu) against the XLA batch-norm math."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import fused_bn
    _require(fused_bn.supports((n, c, hw, hw), jnp.bfloat16),
             f"fused_bn.supports rejects {(n, c, hw, hw)}")

    def kernel(x, scale, bias):
        return fused_bn.fused_bn_act(x, scale, bias, 1e-5, "relu", False)[0]

    def reference(x, scale, bias):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(xf * xf, axis=(0, 2, 3), keepdims=True) - mean * mean
        y = ((xf - mean) * jax.lax.rsqrt(jnp.maximum(var, 0.0) + 1e-5)
             * scale.reshape(1, -1, 1, 1) + bias.reshape(1, -1, 1, 1))
        return jax.nn.relu(y).astype(x.dtype)

    return KernelCase(name, lambda rng: _bn_args(rng, n, c, hw),
                      _with_grads(kernel, 3), _with_grads(reference, 3), 2e-2)


def _conv_bn_case(name, n, ci, hw, co):
    """fused_conv_bn_act, the bottleneck tail (1x1 conv + BN + residual +
    relu), against the repo's own XLA composition `conv_bn_xla`."""
    from paddle_tpu.ops.pallas_kernels import fused_bn
    _require(fused_bn.conv_bn_supports((n, ci, hw, hw), (co, ci, 1, 1), 1),
             f"conv_bn_supports rejects {(n, ci, hw, hw)} -> {co}")

    def kernel(x, w, scale, bias, res):
        return fused_bn.fused_conv_bn_act(x, w, scale, bias, 1e-5, "relu", 1,
                                          True, res)[0]

    def reference(x, w, scale, bias, res):
        return fused_bn.conv_bn_xla(x, w, scale, bias, 1e-5, "relu", 1,
                                    res)[0]

    # looser than the rest: the two sides round the conv output to bf16
    # after different accumulation orders, so a fraction f of relu masks
    # flips and the masked gradients differ by ~sqrt(f) (exact f32 parity
    # is tests/test_fused_bn.py's job, under the interpreter)
    return KernelCase(
        name, lambda rng: _bn_args(rng, n, ci, hw, co=co, residual=True),
        _with_grads(kernel, 5), _with_grads(reference, 5), 6e-2)


def _ssd_case(name, b, t, heads=64, p=64, groups=8, n=128, chunk=128):
    """The Mamba-2 scan's forward and backward kernels against the einsum
    form (ops/ssm_ops.py), at Nemotron's widths: bf16 x, B, C, time steps
    over the published [1e-3, 1e-1], A = -1..-H as the mixer starts."""
    import jax.numpy as jnp

    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.ops.pallas_kernels import ssd_scan
    _require(ssd_scan.supports(t, heads, p, groups, n, chunk),
             f"ssd_scan.supports rejects T {t}, {heads} heads of {p}, "
             f"{groups} groups, state {n}")

    def make_args(rng):
        def normal(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (b, t, heads)))
        return (normal(b, t, heads, p), jnp.asarray(dt, jnp.float32),
                -jnp.arange(1, heads + 1, dtype=jnp.float32),
                normal(b, t, groups, n), normal(b, t, groups, n),
                jnp.ones((heads,), jnp.float32))

    return KernelCase(
        name, make_args,
        _with_grads(lambda *a: ssd_scan.ssd_scan(*a, chunk), 6),
        _with_grads(lambda *a: ssm_ops.ssd_scan_einsum(*a, chunk), 6), 2e-2)


def _grouped_case(name, b, t, d, h, held, experts, k, gated, act):
    """The experts' grouped product (ops/pallas_kernels/grouped_ffn.py)
    against the loops over tiles of parallel/moe.py on the same plan: the
    result, dx, the pair weights' gradient and the matrices' (four Mosaic
    calls: the rows laid out and the walk, forward and backward). bf16
    activations over float32 masters, `held` of `experts` held, top-k by a
    random draw that favours expert 0 (a dozen tiles beside experts of one
    or two)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.common import act_map
    from paddle_tpu.ops.pallas_kernels import grouped_ffn
    from paddle_tpu.parallel import moe
    n = b * t
    _require(grouped_ffn.supports(d, h, gated, jnp.bfloat16, moe.TILE),
             f"grouped_ffn.supports rejects D {d}, H {h}")
    loops = jax.custom_vjp(lambda *a: moe._loop_fwd(*a)[0],
                           nondiff_argnums=(8, 9, 10))
    loops.defvjp(moe._loop_fwd, moe._loop_bwd)

    def make_args(rng):
        def normal(*shape, scale=1.0):
            return jnp.asarray(rng.standard_normal(shape) * scale,
                               jnp.float32)
        logits = rng.standard_normal((n, experts)).astype(np.float32)
        logits[:, 0] += 2.0
        idx = np.argsort(-logits, axis=1)[:, :k].astype(np.int32)
        weight = rng.uniform(0.1, 1.0, (n, k)).astype(np.float32)
        mats = [normal(held, d, h, scale=d ** -0.5),
                normal(held, h, d, scale=h ** -0.5)]
        if gated:
            mats.append(normal(held, d, h, scale=d ** -0.5))
        return (normal(n, d).astype(jnp.bfloat16), jnp.asarray(weight),
                *mats, jnp.asarray(idx))

    def product(form):
        def f(x, weight, w1, w2, *rest):
            w3, idx = rest if gated else (None,) + rest
            plan = moe._dispatch(idx, 0, held, moe.TILE)
            return form(x, weight, w1, None, w2, None, plan, w3,
                        act_map()[act], k, moe.TILE)
        return _with_grads(f, 5 if gated else 4)

    return KernelCase(name, make_args, product(moe._grouped_ffn),
                      product(loops), 2e-2, mosaic_calls=4)


def kernel_cases(batch: Optional[int] = None):
    """The shapes the models put through each kernel: ERNIE (b64, T=512,
    12 heads, [B,1,T] bias), NMT-big (16 heads; causal decoder, block-sparse
    packed self and cross attention), ring attention's causal T=4096 block,
    the four decoder cells' blocked causal calls (LFM2's [64, 8192, 64] on
    8 key/value heads, Nemotron's [64, 8192, 128] on 2, Ouro's
    [32, 4096, 128], JoyAI's 32 heads of 192-wide keys and 128-wide values
    at T 8,192 and at the smoke phase's 1,024, Laguna's 48 heads on 8 of 128
    and its 64 on 8 under a window of 512, at T 8,192 and 1,024),
    ResNet-50's bottleneck tails at batch 128, Nemotron's Mamba-2 scan at
    the benchmark cell's own shape (b2 x T8192), the experts' grouped product
    in both forms (plain relu^2 at Nemotron's 2,688 x 1,856, gated silu at
    Laguna's 2,048 x 512; b2 x T8192 tokens). `batch` overrides every batch
    size (the tier-1 lowering test cuts it to 2); the 7x7 cases keep the 24
    images fused_bn's own shape gate needs (1024 rows, a multiple of 8)."""
    b = (lambda default, least=1: max(batch, least) if batch else default)
    return [
        _dense_case("flash_dense_t512_bias_onepass", b(64), 512, 12,
                    causal=False, with_bias=True),
        _dense_case("flash_dense_t256_causal_onepass", b(16), 256, 16,
                    causal=True, with_bias=False),
        _dense_case("flash_dense_t1024_causal_tiled", b(4), 1024, 16,
                    causal=True, with_bias=False),
        _dense_case("flash_dense_t4096_causal_tiled", b(1), 4096, 16,
                    causal=True, with_bias=False),
        _blocked_causal_case("flash_lfm2_t8192_h32on8_d64", b(2), 8192, 32,
                             8, 64),
        _blocked_causal_case("flash_nemotron_t8192_h32on2_d128", b(2), 8192,
                             32, 2, 128),
        _blocked_causal_case("flash_ouro_t4096_h16_d128", b(2), 4096, 16, 16,
                             128),
        _blocked_causal_case("flash_joyai_t8192_h32_d192_v128", b(2), 8192,
                             32, 32, 192, 128),
        _blocked_causal_case("flash_joyai_t1024_h32_d192_v128", b(2), 1024,
                             32, 32, 192, 128),
        _blocked_causal_case("flash_laguna_t8192_h48on8_d128", b(2), 8192,
                             48, 8, 128),
        _blocked_causal_case("flash_laguna_t8192_h64on8_d128_w512", b(2),
                             8192, 64, 8, 128, window=512),
        _blocked_causal_case("flash_laguna_t1024_h64on8_d128_w512", b(2),
                             1024, 64, 8, 128, window=512),
        _sparse_case("flash_sparse_self_t256_causal", b(16), 256, 256, 16,
                     causal=True),
        _sparse_case("flash_sparse_cross_tq256_tk384", b(16), 256, 384, 16,
                     causal=False),
        _bn_case("fused_bn_act_128x64x56x56", b(128), 64, 56),
        _bn_case("fused_bn_act_128x2048x7x7", b(128, 24), 2048, 7),
        _conv_bn_case("fused_conv_bn_act_128x64x56x56_to_256", b(128), 64,
                      56, 256),
        _conv_bn_case("fused_conv_bn_act_128x512x7x7_to_2048", b(128, 24),
                      512, 7, 2048),
        _ssd_case("ssd_scan_t8192_h64x64_g8_n128", b(2), 8192),
        _grouped_case("grouped_ffn_plain_d2688_h1856_top6", b(2), 8192, 2688,
                      1856, 4, 64, 6, False, "relu2"),
        _grouped_case("grouped_ffn_gated_d2048_h512_top8", b(2), 8192, 2048,
                      512, 8, 64, 8, True, "silu"),
    ]


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def run_kernel_case(case: KernelCase) -> dict:
    import jax

    args = case.make_args(np.random.RandomState(0))
    lowered = jax.jit(case.kernel).lower(*args)
    n_mosaic = lowered.as_text().count("tpu_custom_call")
    got = lowered.compile()(*args)
    want = jax.jit(case.reference)(*args)
    errs = [_rel_err(g, w) for g, w in zip(got, want)]
    finite = all(bool(np.isfinite(np.asarray(g, np.float32)).all())
                 for g in got)
    ok = (n_mosaic == case.mosaic_calls if case.mosaic_calls
          else n_mosaic > 0) and finite and max(errs) <= case.tol
    return {"name": case.name, "ok": ok, "mosaic_calls": n_mosaic,
            "finite": finite, "rel_err": [round(e, 5) for e in errs],
            "tol": case.tol}


def dropout_check() -> dict:
    """The in-kernel PRNG exists only on the TPU, so dropout has no XLA twin
    to compare with. With q = k = 0 every probability is 1/T, and with
    v = 1 each output element is (kept keys) / (T (1 - rate)): its mean
    gives the keep fraction. With a cotangent of ones, sum(dv) over keys
    equals sum(out) over queries only if the backward kernel regenerated the
    forward's masks."""
    import jax
    import jax.numpy as jnp

    b, t, nh, rate = 8, 512, 12, 0.1
    h = nh * 64
    q = jnp.zeros((b, t, h), jnp.float32)
    v = jnp.ones((b, t, h), jnp.float32)
    bias = jnp.zeros((b, 1, t), jnp.float32)

    def f(v, key):
        return _fa().flash_attention_packed(
            q, q, v, nh, bias=bias, dropout_rate=rate, dropout_key=key)

    fwd = jax.jit(f)
    fwd_bwd = jax.jit(lambda v, key: jax.value_and_grad(
        lambda v: jnp.sum(f(v, key)))(v))
    n_mosaic = fwd_bwd.lower(v, jax.random.key(0)).as_text().count(
        "tpu_custom_call")
    out = fwd(v, jax.random.key(0))
    total, dv = fwd_bwd(v, jax.random.key(0))
    out2 = fwd(v, jax.random.key(1))
    keep = float(jnp.mean(out)) * (1.0 - rate)
    per_head = np.asarray(out[:, :, ::64]).reshape(b, t, nh)
    fwd_bwd_gap = abs(float(jnp.sum(dv)) - float(total)) / float(total)
    ok = (n_mosaic > 0 and abs(keep - (1.0 - rate)) < 5e-3
          and per_head.std() > 0 and fwd_bwd_gap < 1e-4
          and not bool(jnp.array_equal(out, out2)))
    return {"name": "flash_dense_t512_dropout0.1", "ok": ok,
            "mosaic_calls": n_mosaic, "keep_fraction": round(keep, 5),
            "expected": 1.0 - rate, "mask_varies": bool(per_head.std() > 0),
            "fwd_bwd_mask_gap": fwd_bwd_gap,
            "key_changes_mask": not bool(jnp.array_equal(out, out2))}


def blocked_dropout_check() -> dict:
    """A blocked call (2 x 2 blocks of 512) under dropout. The backward
    kernel works on transposed scores and draws the forward's [bq, bk] mask
    transposed, once for all three gradients, so a count of kept entries
    would not tell a wrong orientation. With q = k = 0 every probability is
    1/T, and with feature f of v one-hot at key k_f, out[q, f] is
    keep[q, k_f] / (T (1 - rate)); with a cotangent of ones dv[k_f, f] is
    the same sum over q of the BACKWARD's mask: column k_f of both masks, 64
    columns a head across both k blocks, equal to float32's rounding (1/T is
    a power of two, so both kernels round the kept probability to the same
    bf16)."""
    import jax
    import jax.numpy as jnp

    b, t, nh, d, rate = 2, 1024, 4, 64, 0.1
    k_of = (np.arange(d) * 37 + 5) % t
    v = np.zeros((b, t, nh, d), "float32")
    v[:, k_of, :, np.arange(d)] = 1.0
    v = jnp.asarray(v.reshape(b, t, nh * d))
    q = jnp.zeros_like(v)

    def f(v, key):
        return _fa().flash_attention_packed(
            q, q, v, nh, dropout_rate=rate, dropout_key=key)

    # forward and the one backward kernel
    fwd_bwd = jax.jit(lambda v, key: jax.value_and_grad(
        lambda v: jnp.sum(f(v, key)))(v))
    n_mosaic = fwd_bwd.lower(v, jax.random.key(0)).as_text().count(
        "tpu_custom_call")
    out = jax.jit(f)(v, jax.random.key(0))
    _, dv = fwd_bwd(v, jax.random.key(0))
    col_fwd = np.asarray(out, np.float64).reshape(b, t, nh, d).sum(axis=1)
    col_bwd = np.asarray(dv, np.float64).reshape(b, t, nh, d)[
        :, k_of, :, np.arange(d)].transpose(1, 2, 0)
    gap = float(np.abs(col_fwd - col_bwd).max())
    # undropped, every column sums to 1; a kept share of 0.9 of 1,024
    # entries leaves it within a few hundredths of 1, and not at 1
    varies = float(np.abs(col_fwd - 1.0).max())
    ok = n_mosaic == 2 and gap < 1e-5 and 1e-3 < varies < 0.2
    return {"name": "flash_dense_t1024_blocked_dropout0.1", "ok": ok,
            "mosaic_calls": n_mosaic, "fwd_bwd_column_gap": gap,
            "columns_differ_from_undropped": varies}


def phase_kernels(args):
    fa = _fa()
    from paddle_tpu.ops.pallas_kernels import fused_bn
    _require(not fa.FORCE_PALLAS_INTERPRET
             and not fused_bn.FORCE_PALLAS_INTERPRET,
             "a FORCE_PALLAS_INTERPRET flag is set: kernels would not compile")
    results = []
    for case in kernel_cases():
        res = run_kernel_case(case)
        results.append(res)
        print(f"kernel {res['name']}: {'PASS' if res['ok'] else 'FAIL'} "
              f"compiled ({res['mosaic_calls']} Mosaic calls), worst rel err "
              f"{max(res['rel_err']):.2e} (tol {res['tol']:.0e})",
              flush=True)
        gc.collect()
    res = dropout_check()
    results.append(res)
    print(f"kernel {res['name']}: {'PASS' if res['ok'] else 'FAIL'} compiled "
          f"({res['mosaic_calls']} Mosaic calls), keep fraction "
          f"{res['keep_fraction']} (want {res['expected']}), fwd/bwd mask gap "
          f"{res['fwd_bwd_mask_gap']:.1e}", flush=True)
    res = blocked_dropout_check()
    results.append(res)
    print(f"kernel {res['name']}: {'PASS' if res['ok'] else 'FAIL'} compiled "
          f"({res['mosaic_calls']} Mosaic calls), forward and backward mask "
          f"columns differ by {res['fwd_bwd_column_gap']:.1e}", flush=True)
    bad = [r["name"] for r in results if not r["ok"]]
    _require(not bad, f"kernels failed: {', '.join(bad)}")
    return {"kernels": results}


# ---------------------------------------------------------------------------
# deepfm: the second model, on its default (XLA gather/scatter) path
# ---------------------------------------------------------------------------

def phase_deepfm(args):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import deepfm

    vocab, batch = DEEPFM_VOCAB, DEEPFM_BATCH
    main, startup, _, loss, _ = deepfm.build_train_program(
        vocab_size=vocab, is_sparse=True, fused_table=True,
        embedding_optimizer="adagrad",
        packed_rows={"rows_per_step": batch * 26})
    rng = np.random.RandomState(0)
    feed = {
        "sparse_ids": jnp.asarray(
            rng.randint(0, vocab, (batch, 26)).astype("int32")),
        "dense": jnp.asarray(rng.rand(batch, 13).astype("float32")),
        "label": jnp.asarray(rng.randint(0, 2, (batch, 1)).astype("float32")),
    }
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        losses = [exe.run(main, feed=feed, fetch_list=[loss],
                          return_numpy=False)[0]
                  for _ in range(DEEPFM_STEPS)]
        vals = [float(np.asarray(v)) for v in losses]
        wall_s = time.perf_counter() - t0
        _require(all(np.isfinite(vals)), f"non-finite DeepFM loss in {vals}")
        _require(vals[-1] < vals[0],
                 f"DeepFM loss did not fall on a fixed batch: {vals}")
        _require(_platforms(losses[-1]) == {"tpu"},
                 f"DeepFM loss lives on {_platforms(losses[-1])}, not tpu")
        tables = [(n, v) for n, v in
                  ((n, scope.find_var(n)) for n in scope.var_names())
                  if getattr(v, "shape", ())[:1] == (vocab,)]
        _require(tables and all(_platforms(v) == {"tpu"} for _, v in tables),
                 "no [vocab, ...] table resident on the tpu")
        peak = _mem_stat(jax.devices()[0], "peak_bytes_in_use")
    out = {"config": {"vocab": vocab, "batch": batch,
                      "optimizer": "adagrad, packed rows"},
           "losses": [round(v, 5) for v in vals],
           "tables": {n: [list(v.shape), str(v.dtype)] for n, v in tables},
           "peak_bytes_in_use_process": peak,
           "setup": {"startup_compile_and_steps_s": round(wall_s, 2)}}
    print(f"deepfm: {vocab} rows b{batch} packed Adagrad, loss "
          f"{vals[0]:.5f} -> {vals[-1]:.5f} over {DEEPFM_STEPS} steps "
          f"(startup+compile+steps {wall_s:.1f} s, set-up fact)")
    del losses, tables, scope, exe
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# looped: a decoder whose layers run twice over the same weights
# ---------------------------------------------------------------------------

def phase_looped(args):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import ouro

    cfg = ouro.OuroConfig(num_layers=LOOPED_LAYERS,
                          total_ut_steps=LOOPED_PASSES)

    def opt():
        return mp.decorate(fluid.optimizer.Adam(1e-4), dtype="bfloat16",
                           use_dynamic_loss_scaling=False)

    with fluid.unique_name.guard():
        main, startup, _, loss, (share, entropy) = ouro.build_pretrain_program(
            cfg, 1, LOOPED_SEQ, opt)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, LOOPED_SEQ + 1)).astype("int32")
    feed = {"ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:, None])}
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        fetched = [exe.run(main, feed=feed, fetch_list=[loss, share, entropy],
                           return_numpy=False)
                   for _ in range(LOOPED_STEPS)]
        vals = [float(np.asarray(f[0])) for f in fetched]
        wall_s = time.perf_counter() - t0
        shares = np.asarray(fetched[-1][1], dtype=np.float64)
        entropy_nats = float(np.asarray(fetched[-1][2]))
        matrices = [n for n in scope.var_names() if n.endswith(".qkv.w")]
    _require(all(np.isfinite(vals)), f"non-finite looped loss in {vals}")
    _require(vals[-1] < vals[0],
             f"looped loss did not fall on a fixed batch: {vals}")
    _require(_platforms(fetched[-1][0]) == {"tpu"},
             f"looped loss lives on {_platforms(fetched[-1][0])}, not tpu")
    _require(shares.shape == (LOOPED_PASSES,)
             and abs(shares.sum() - 1.0) < 1e-5,
             f"exit shares {shares.tolist()} do not sum to 1")
    _require(len(matrices) == LOOPED_LAYERS,
             f"{len(matrices)} q/k/v matrices for {LOOPED_LAYERS} layers "
             f"run {LOOPED_PASSES} times: {sorted(matrices)}")
    print(f"looped: {LOOPED_LAYERS} layers x {LOOPED_PASSES} passes b1 x "
          f"{LOOPED_SEQ}, {ouro.param_count(cfg) / 1e6:.1f}M parameters, loss "
          f"{vals[0]:.5f} -> {vals[-1]:.5f} over {LOOPED_STEPS} steps "
          f"(startup+compile+steps {wall_s:.1f} s, set-up fact)")
    del fetched, scope, exe
    gc.collect()
    return {"config": {"layers": LOOPED_LAYERS, "passes": LOOPED_PASSES,
                       "seq": LOOPED_SEQ,
                       "parameters": ouro.param_count(cfg)},
            "losses": [round(v, 5) for v in vals],
            "exit_share": [round(float(x), 6) for x in shares],
            "exit_entropy": round(entropy_nats, 6),
            "setup": {"startup_compile_and_steps_s": round(wall_s, 2)}}


# ---------------------------------------------------------------------------
# lfm2: gated short convolutions, GQA with q/k norm, gated experts, tied head
# ---------------------------------------------------------------------------

def phase_lfm2(args):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import lfm2

    cfg = lfm2.Lfm2Config(vocab_size=8192, layer_types=list(LFM2_LAYERS),
                          num_dense_layers=1, experts_held=(0, 8))

    def opt():
        return mp.decorate(fluid.optimizer.Adam(1e-4), dtype="bfloat16",
                           use_dynamic_loss_scaling=False)

    with fluid.unique_name.guard():
        main, startup, _, loss, counters = lfm2.build_pretrain_program(
            cfg, 1, LFM2_SEQ, opt)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, LFM2_SEQ + 1)).astype("int32")
    feed = {"ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:, None])}
    fetch = [loss] + [v for _, tokens, pairs in counters
                      for v in (tokens, pairs)]
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        fetched = [exe.run(main, feed=feed, fetch_list=fetch,
                           return_numpy=False) for _ in range(LFM2_STEPS)]
        vals = [float(np.asarray(f[0])) for f in fetched]
        wall_s = time.perf_counter() - t0
        tables = [n for n in scope.var_names()
                  if n in ("embed.w", "lm_head.w")]
    _require(all(np.isfinite(vals)), f"non-finite lfm2 loss in {vals}")
    _require(vals[-1] < vals[0],
             f"lfm2 loss did not fall on a fixed batch: {vals}")
    _require(_platforms(fetched[-1][0]) == {"tpu"},
             f"lfm2 loss lives on {_platforms(fetched[-1][0])}, not tpu")
    _require(tables == ["embed.w"],
             f"a tied head has one table, the scope holds {tables}")
    held = []
    for tokens, pairs in zip(fetched[-1][1::2], fetched[-1][2::2]):
        tokens, pairs = np.asarray(tokens), int(np.asarray(pairs))
        _require(tokens.shape == (8,) and tokens.sum() == pairs
                 and 0 < pairs <= LFM2_SEQ * cfg.num_experts_per_tok,
                 f"held pairs {pairs} against per-expert {tokens.tolist()}")
        held.append(pairs)
    _require(len(held) == len(LFM2_LAYERS) - 1,
             f"{len(held)} expert layers of {len(LFM2_LAYERS) - 1}")
    print(f"lfm2: {len(LFM2_LAYERS)} layers b1 x {LFM2_SEQ}, "
          f"{lfm2.param_count(cfg) / 1e6:.1f}M parameters, loss "
          f"{vals[0]:.5f} -> {vals[-1]:.5f} over {LFM2_STEPS} steps, pairs "
          f"held a layer {held} of {LFM2_SEQ * cfg.num_experts_per_tok} "
          f"(startup+compile+steps {wall_s:.1f} s, set-up fact)")
    del fetched, scope, exe
    gc.collect()
    return {"config": {"layer_types": LFM2_LAYERS, "seq": LFM2_SEQ,
                       "parameters": lfm2.param_count(cfg)},
            "losses": [round(v, 5) for v in vals], "pairs_held": held,
            "setup": {"startup_compile_and_steps_s": round(wall_s, 2)}}


# ---------------------------------------------------------------------------
# joyai: latent attention, gated experts beside a shared one, an MTP module
# ---------------------------------------------------------------------------

JOYAI_SEQ, JOYAI_STEPS = 1024, 3


def phase_joyai(args):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import joyai_flash

    cfg = joyai_flash.JoyaiFlashConfig(
        vocab_size=16160, num_hidden_layers=2, experts_held=(0, 16))

    def opt():
        return mp.decorate(fluid.optimizer.Adam(1e-4), dtype="bfloat16",
                           use_dynamic_loss_scaling=False)

    with fluid.unique_name.guard():
        main, startup, _, loss, counters, terms = (
            joyai_flash.build_pretrain_program(cfg, 1, JOYAI_SEQ, opt))
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, JOYAI_SEQ + 1)).astype("int32")
    feed = {"ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:, None])}
    fetch = ([loss, terms["main"], terms["mtp"]]
             + [v for _, tokens, pairs in counters for v in (tokens, pairs)])
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        fetched = [exe.run(main, feed=feed, fetch_list=fetch,
                           return_numpy=False) for _ in range(JOYAI_STEPS)]
        vals = [[float(np.asarray(v)) for v in f[:3]] for f in fetched]
        wall_s = time.perf_counter() - t0
        slots = [n for n in scope.var_names() if n.startswith(
            ("embed.w_AdamOptimizer_moment1",
             "lm_head.w_AdamOptimizer_moment1"))]
    _require(np.isfinite(vals).all(), f"non-finite joyai loss in {vals}")
    for term in range(3):         # the sum, the trunk's term, the module's
        _require(vals[-1][term] < vals[0][term],
                 f"joyai loss term {term} did not fall on a fixed batch: "
                 f"{vals}")
    _require(abs(vals[0][0] - vals[0][1] - cfg.mtp_loss_weight * vals[0][2])
             < 1e-3, f"the fetched loss is not main + weight * mtp: {vals[0]}")
    _require(_platforms(fetched[-1][0]) == {"tpu"},
             f"joyai loss lives on {_platforms(fetched[-1][0])}, not tpu")
    _require(len(slots) == 2,
             f"the table and the head matrix have one Adam slot each, the "
             f"scope holds {slots}")
    held = []
    for tokens, pairs in zip(fetched[-1][3::2], fetched[-1][4::2]):
        tokens, pairs = np.asarray(tokens), int(np.asarray(pairs))
        _require(tokens.shape == (16,) and tokens.sum() == pairs
                 and 0 < pairs <= JOYAI_SEQ * cfg.num_experts_per_tok,
                 f"held pairs {pairs} against per-expert {tokens.tolist()}")
        held.append(pairs)
    _require(len(held) == 2, f"{len(held)} expert layers of 2 (one of the "
             f"trunk's, the module's)")
    print(f"joyai: dense layer, expert layer and the MTP module b1 x "
          f"{JOYAI_SEQ}, {joyai_flash.param_count(cfg) / 1e6:.1f}M "
          f"parameters, loss (sum, main, mtp) {vals[0]} -> {vals[-1]} over "
          f"{JOYAI_STEPS} steps, pairs held a layer {held} of "
          f"{JOYAI_SEQ * cfg.num_experts_per_tok} "
          f"(startup+compile+steps {wall_s:.1f} s, set-up fact)")
    del fetched, scope, exe
    gc.collect()
    return {"config": {"seq": JOYAI_SEQ,
                       "parameters": joyai_flash.param_count(cfg)},
            "losses": [[round(v, 5) for v in row] for row in vals],
            "pairs_held": held,
            "setup": {"startup_compile_and_steps_s": round(wall_s, 2)}}


# ---------------------------------------------------------------------------
# laguna: window and full attention at two head counts, the output gate,
# YaRN on half a head, gated experts beside a shared one
# ---------------------------------------------------------------------------

LAGUNA_SEQ, LAGUNA_STEPS = 1024, 3


def phase_laguna(args):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import laguna

    cfg = laguna.LagunaConfig(vocab_size=12544, num_hidden_layers=2,
                              experts_held=(0, 32))

    def opt():
        return mp.decorate(fluid.optimizer.Adam(1e-4), dtype="bfloat16",
                           use_dynamic_loss_scaling=False)

    with fluid.unique_name.guard():
        main, startup, _, loss, counters = laguna.build_pretrain_program(
            cfg, 1, LAGUNA_SEQ, opt)
    kinds = [op.attrs.get("window") for op in main.global_block().ops
             if op.type == "flash_attention"]
    _require(kinds == [None, cfg.sliding_window],
             f"a full layer and a window layer, the ops say {kinds}")
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, LAGUNA_SEQ + 1)).astype("int32")
    feed = {"ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:, None])}
    fetch = [loss] + [v for _, tokens, pairs in counters
                      for v in (tokens, pairs)]
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        fetched = [exe.run(main, feed=feed, fetch_list=fetch,
                           return_numpy=False) for _ in range(LAGUNA_STEPS)]
        vals = [float(np.asarray(f[0])) for f in fetched]
        wall_s = time.perf_counter() - t0
    _require(np.isfinite(vals).all(), f"non-finite laguna loss in {vals}")
    _require(vals[-1] < vals[0],
             f"laguna loss did not fall on a fixed batch: {vals}")
    _require(_platforms(fetched[-1][0]) == {"tpu"},
             f"laguna loss lives on {_platforms(fetched[-1][0])}, not tpu")
    tokens, pairs = (np.asarray(fetched[-1][1]),
                     int(np.asarray(fetched[-1][2])))
    _require(tokens.shape == (32,) and tokens.sum() == pairs
             and 0 < pairs <= LAGUNA_SEQ * cfg.num_experts_per_tok,
             f"held pairs {pairs} against per-expert {tokens.tolist()}")
    print(f"laguna: a full layer (48 heads, dense MLP) and a window layer "
          f"(64 heads, window {cfg.sliding_window}, experts) b1 x "
          f"{LAGUNA_SEQ}, {laguna.param_count(cfg) / 1e6:.1f}M parameters, "
          f"loss {vals[0]} -> {vals[-1]} over {LAGUNA_STEPS} steps, pairs "
          f"held {pairs} of {LAGUNA_SEQ * cfg.num_experts_per_tok} "
          f"(startup+compile+steps {wall_s:.1f} s, set-up fact)")
    del fetched, scope, exe
    gc.collect()
    return {"config": {"seq": LAGUNA_SEQ,
                       "parameters": laguna.param_count(cfg)},
            "losses": [round(v, 5) for v in vals], "pairs_held": pairs,
            "setup": {"startup_compile_and_steps_s": round(wall_s, 2)}}


# ---------------------------------------------------------------------------
# kda: the chunked gated delta rule against the recurrence, one layer's call
# ---------------------------------------------------------------------------

KDA_BATCH, KDA_SEQ, KDA_HEADS, KDA_DIM, KDA_CHUNK = 2, 8192, 32, 128, 64
KDA_TOL = 2e-2


def _delta_rule_recurrence(q, k, v, g, beta, scale, segment=64):
    """The rule position by position in float32, every sum written out (no
    matrix unit): q, k, g [B, T, H, K], v [B, T, H, V], beta [B, T, H]. The
    backward pass keeps the state entering each `segment` of positions."""
    import jax
    import jax.numpy as jnp

    def step(state, inp):                   # state [B, H, K, V]
        qt, kt, vt, gt, bt = inp
        state = jnp.exp(gt)[..., None] * state
        recalled = jnp.sum(state * kt[..., None], axis=-2)
        state = state + (bt[..., None, None] * kt[..., None]
                         * (vt - recalled)[..., None, :])
        return state, scale * jnp.sum(state * qt[..., None], axis=-2)

    @jax.checkpoint
    def run(state, inputs):
        return jax.lax.scan(step, state, inputs)

    b, t, h, dk = k.shape
    split = lambda x: jnp.moveaxis(x, 1, 0).reshape(
        (t // segment, segment) + x.shape[:1] + x.shape[2:])
    zero = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(run, zero, tuple(map(split, (q, k, v, g, beta))))
    return jnp.moveaxis(out.reshape((t,) + out.shape[2:]), 0, 1)


KDA_L2_EPS = 1e-6


def kda_case(batch=KDA_BATCH, seq=KDA_SEQ):
    """(args, the op's and the oracle's (o, seven gradients), the op's decay
    floor and the form it takes): one KDA layer's call of
    `ops/linear_attn_ops.py` as `models/kimi_linear.py` makes it at the
    benchmark cell's shape: bf16 q, k, v (q and k normalised inside), the
    decay gate's raw values in bf16 with A_log and dt_bias (A in [1, 16]
    times steps of softplus(raw + dt_bias) between 1e-3 and 0.4: the
    strongest heads' chunks pass float32's e^-88), beta a sigmoid's draw.
    The oracle normalises and gates the same bf16 values in float32 (q and k
    rounded to bf16 again, as the op's are) and walks them position by
    position."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attn_ops
    rng = np.random.RandomState(0)
    shape = (batch, seq, KDA_HEADS, KDA_DIM)
    bf16, f32 = jnp.bfloat16, jnp.float32
    q, k, v = (jnp.asarray(rng.standard_normal(shape), bf16)
               for _ in range(3))
    a_log = jnp.asarray(np.log(rng.uniform(1.0, 16.0, KDA_HEADS)), f32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.4), shape[2:]))
    dt_bias = jnp.asarray(dt + np.log(-np.expm1(-dt)), f32)
    raw = jnp.asarray(0.5 * rng.standard_normal(shape), bf16)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.standard_normal(shape[:3]))), f32)
    scale = KDA_DIM ** -0.5
    rule = lambda *a: linear_attn_ops.kda_rule(
        *a[:5], (a[5], a[6]), KDA_CHUNK, scale, KDA_L2_EPS)

    def recurrence(q, k, v, raw, beta, a_log, dt_bias):
        unit = lambda x: (x.astype(f32) / jnp.maximum(jnp.linalg.norm(
            x.astype(f32), axis=-1, keepdims=True), KDA_L2_EPS)).astype(bf16)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            raw.astype(f32) + dt_bias)
        return _delta_rule_recurrence(
            unit(q).astype(f32), unit(k).astype(f32), v.astype(f32), g, beta,
            scale)

    args = (q, k, v, raw, beta, a_log, dt_bias)
    return (args, _with_grads(lambda *a: rule(*a)[0], 7),
            _with_grads(recurrence, 7), lambda: rule(*args)[1],
            linear_attn_ops.rule_path(q, v, KDA_CHUNK))


def phase_kda(args):
    import jax

    case_args, op, oracle, floor, path = kda_case()
    print(f"kda: the op takes the form {path!r}", flush=True)
    _require(path == "pallas",
             f"at the cell's shape on a TPU the rule took {path!r}, not its "
             "kernels")
    t0 = time.perf_counter()
    got = jax.block_until_ready(jax.jit(op)(*case_args))
    op_s = time.perf_counter() - t0
    floor = float(jax.jit(floor)())
    want = jax.jit(oracle)(*case_args)
    errs = [_rel_err(g, w) for g, w in zip(got, want)]
    finite = all(bool(np.isfinite(np.asarray(g, np.float32)).all())
                 for g in got)
    _require(_platforms(got[0]) == {"tpu"},
             f"the rule's result lives on {_platforms(got[0])}, not tpu")
    names = ("o", "dq", "dk", "dv", "dg", "dbeta", "dA_log", "ddt_bias")
    print(f"kda: the chunked gated delta rule b{KDA_BATCH} x T{KDA_SEQ}, "
          f"{KDA_HEADS} heads of {KDA_DIM}, chunk {KDA_CHUNK}, decay floor "
          f"{floor:.1f} nats, against the recurrence: rel err "
          + ", ".join(f"{n} {e:.2e}" for n, e in zip(names, errs))
          + f" (tol {KDA_TOL:.0e}; compile+run {op_s:.1f} s, set-up fact)",
          flush=True)
    _require(finite and max(errs) <= KDA_TOL,
             f"the rule differs from the recurrence: {errs}")
    _require(floor < -88.0,
             f"the case's decay floor {floor} is not past float32's e^-88")
    del got, want
    gc.collect()
    return {"shape": [KDA_BATCH, KDA_SEQ, KDA_HEADS, KDA_DIM], "path": path,
            "decay_floor": round(floor, 2),
            "rel_err": dict(zip(names, (round(e, 5) for e in errs))),
            "tol": KDA_TOL, "setup": {"compile_and_run_s": round(op_s, 2)}}


# ---------------------------------------------------------------------------
# dp4: the same ERNIE program, data-parallel over four chips
# ---------------------------------------------------------------------------

def _run_steps(exe, program, feed, loss, steps):
    vals = []
    for _ in range(steps):
        (lv,) = exe.run(program, feed=feed, fetch_list=[loss])
        vals.append(float(lv))
    return vals


def phase_dp4(args):
    import jax

    import paddle_tpu as fluid

    devs = jax.devices()[:4]
    n = len(devs)
    exe = fluid.Executor(fluid.TPUPlace())

    # (a) 64 per chip, dropout on: split, shardings, memory spread
    batch = DP4_PER_CHIP * n
    cfg, main, startup, loss = ernie_program(batch, ERNIE_SEQ, 0.1)
    feed = ernie_feed(cfg, batch, ERNIE_SEQ)
    cp = fluid.CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    _require(list(cp._mesh.devices.flat) == devs,
             f"mesh devices {list(cp._mesh.devices.flat)} != {devs}")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        t0 = time.perf_counter()
        vals = _run_steps(exe, cp, feed, loss, 3)
        wall_s = time.perf_counter() - t0
        _require(all(np.isfinite(vals)), f"non-finite dp4 loss in {vals}")

        unsharded = [
            name for name in scope.var_names()
            if isinstance(scope.find_var(name), jax.Array)
            and len(scope.find_var(name).sharding.device_set) != n]
        _require(not unsharded,
                 f"state not placed on all {n} devices: {unsharded[:8]}")
        n_state = len(scope.var_names())

        # the compiled executable says how the feed is split: re-lower the
        # cached jit on the live state (the compile itself is a cache hit)
        (step_fn,) = cp._cache.values()
        state = {v.name: scope.find_var(v.name)
                 for v in main.list_vars()
                 if v.persistable and scope.has_var(v.name)}
        feed_arrays = {k: np.asarray(v) for k, v in feed.items()}
        compiled = step_fn.lower(
            state, feed_arrays, scope.find_var("@RNG_STATE@")).compile()
        feed_sh = compiled.input_shardings[0][1]
        split = {k: list(feed_sh[k].shard_shape(np.shape(v)))
                 for k, v in feed_arrays.items()}
        bad = {k: s for k, s in split.items()
               if s[0] * n != np.shape(feed_arrays[k])[0]
               or len(feed_sh[k].device_set) != n}
        _require(not bad, f"feeds not split {n} ways on the batch dim: {bad}")
        n_mosaic = compiled.as_text().count("tpu_custom_call")
        _require(n_mosaic > 0, "no tpu_custom_call in the compiled dp4 step")
        hbm = _hbm_by_xla(compiled)

        in_use = [_mem_stat(d, "bytes_in_use") for d in devs]
        _require(min(in_use) > 0 and max(in_use) <= 1.5 * min(in_use),
                 f"bytes_in_use not spread evenly over the chips: {in_use}")
        peaks = [_mem_stat(d, "peak_bytes_in_use") for d in devs]
    print(f"dp4: ERNIE-base b{batch} ({DP4_PER_CHIP}/chip) loss "
          f"{vals[0]:.4f} -> {vals[-1]:.4f}; feed shards {split['src_ids']} "
          f"of {list(np.shape(feed['src_ids']))}; {n_state} state arrays on "
          f"all {n} devices; {n_mosaic} Mosaic calls")
    print("dp4: bytes_in_use per chip "
          + ", ".join(f"{b / 2**30:.2f}" for b in in_use) + " GiB (max/min "
          f"{max(in_use) / min(in_use):.2f}); peak "
          + ", ".join(f"{b / 2**30:.2f}" for b in peaks) + " GiB; "
          f"compile + 3 steps {wall_s:.1f} s (set-up fact); XLA's memory "
          f"analysis: {hbm['live_bytes'] / 2**30:.2f} GiB per chip")
    out = {"per_chip_batch": DP4_PER_CHIP, "losses": vals,
           "feed_shard_shapes": split, "state_arrays": n_state,
           "mosaic_calls_in_step": n_mosaic, "bytes_in_use": in_use,
           "peak_bytes_in_use": peaks, "hbm_by_xla_memory_analysis": hbm}
    del compiled, state, step_fn, scope, cp
    gc.collect()

    # (b) dropout off, global batch 64: dp4 against one chip. The startup
    # program draws from its own seed, so two scopes start identical.
    cfg, main, startup, loss = ernie_program(DP4_EQ_BATCH, ERNIE_SEQ, 0.0)
    feed = ernie_feed(cfg, DP4_EQ_BATCH, ERNIE_SEQ)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        one = _run_steps(exe, main, feed, loss, DP4_EQ_STEPS)
    gc.collect()
    cp = fluid.CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        dp = _run_steps(exe, cp, feed, loss, DP4_EQ_STEPS)
    rel = [abs(a - b) / abs(b) for a, b in zip(dp, one)]
    print(f"dp4: dropout off, global b{DP4_EQ_BATCH}: dp4 losses "
          f"{[round(v, 4) for v in dp]} vs one chip "
          f"{[round(v, 4) for v in one]} (max rel diff {max(rel):.1e}, "
          f"tol {DP4_EQ_RTOL:.0e})")
    _require(all(np.isfinite(dp)) and max(rel) <= DP4_EQ_RTOL,
             f"dp4 losses {dp} differ from one-chip losses {one}")
    out["equality"] = {"dp4": dp, "one_chip": one, "max_rel_diff": max(rel)}
    return out


# ---------------------------------------------------------------------------

PHASES_ONE_CHIP = [("device", phase_device), ("trainer", phase_trainer),
                   ("kernels", phase_kernels), ("deepfm", phase_deepfm),
                   ("looped", phase_looped), ("lfm2", phase_lfm2),
                   ("joyai", phase_joyai), ("laguna", phase_laguna),
                   ("kda", phase_kda)]
PHASES_FOUR_CHIPS = [("device", phase_device), ("dp4", phase_dp4)]


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    phases = PHASES_FOUR_CHIPS if args.chips == 4 else PHASES_ONE_CHIP
    facts = {}
    t_start = time.perf_counter()
    for name, fn in phases:
        print(f"== phase {name}", flush=True)
        t0 = time.perf_counter()
        try:
            facts[name] = fn(args)
        except Exception as e:
            # the run ends here: nothing carries on past a failed phase
            traceback.print_exc()
            sys.stderr.flush()
            print(f"chip_smoke: FAILED in phase {name}: "
                  f"{type(e).__name__}: {str(e)[:400]}", flush=True)
            return 1
        facts[name]["phase_seconds"] = round(time.perf_counter() - t0, 1)
    facts["total_seconds"] = round(time.perf_counter() - t_start, 1)
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"chip_smoke_chips{args.chips}.json")
    with open(path, "w") as f:
        json.dump(facts, f, indent=1)
    print(f"chip_smoke: all phases passed in {facts['total_seconds']} s; "
          f"details in {path}")
    dev = facts["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
