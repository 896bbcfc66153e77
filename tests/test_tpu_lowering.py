"""Every Pallas entry point a model can reach must lower for the TPU.

``jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic translation from a CPU-only host: block-spec and layout
rules, unsupported primitives and the "Mosaic kernels cannot be
automatically partitioned" refusal under a mesh all surface here (the
sparse-Adagrad row kernels removed in PR 21 failed exactly this check from
the day they were written). It is NOT a substitute for the chip: Mosaic
itself does not run, so VMEM limits, tiling and numerics are only proven by
``chip_smoke.py``, whose kernel cases (cut to batch 2) this test reuses.

The second half compiles for a described v5e with the TPU's own compiler
(`one_chip`): the kernels at the cells' widths and the small steps here, the
experts' grouped product in `test_tpu_lowering_experts.py` and the cells'
whole steps in `test_tpu_lowering_steps.py`, each a file of its own because
under `--dist loadfile` a file is one worker's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
import paddle_tpu as fluid
from tpu_lowering_base import (_dispatch_as_on_tpu, fa,  # noqa: F401
                               one_chip, ssd)


def _lower_for_tpu(fn, *args):
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    return jax.jit(fn).trace(*structs).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize(
    "case", chip_smoke.kernel_cases(batch=2), ids=lambda c: c.name)
def test_smoke_kernel_case_lowers_for_tpu(case):
    args = case.make_args(np.random.RandomState(0))
    text = _lower_for_tpu(case.kernel, *args)
    assert "tpu_custom_call" in text
    if case.mosaic_calls:   # the cells' blocked calls: forward, one backward
        assert text.count("tpu_custom_call") == case.mosaic_calls


def test_flash_dropout_kernels_lower_for_tpu():
    """The in-kernel PRNG (pltpu.prng_*) path, forward and backward."""
    q = jnp.zeros((2, 512, 768), jnp.bfloat16)
    bias = jnp.zeros((2, 1, 512), jnp.float32)

    def loss(q, key):
        return jnp.sum(fa.flash_attention_packed(
            q, q, q, 12, bias=bias, dropout_rate=0.1,
            dropout_key=key).astype(jnp.float32))

    text = _lower_for_tpu(jax.grad(loss), q, jax.random.key(0))
    assert text.count("tpu_custom_call") >= 2


@pytest.fixture(scope="module")
def dp_step_text():
    """The data-parallel pretraining step, lowered for the TPU."""
    from paddle_tpu.core.executor import convert_feed_value
    from paddle_tpu.models import bert

    mp = pytest.MonkeyPatch()
    mp.setattr(fa, "_on_tpu", lambda: True)
    try:
        cfg = bert.BertConfig(num_layers=1, hidden_size=128, num_heads=2,
                              ffn_size=256, vocab_size=100,
                              max_position=128)
        with fluid.unique_name.guard():
            main, startup, _, loss = bert.build_pretrain_program(
                cfg, 8, 128,
                optimizer_factory=lambda: fluid.optimizer.SGD(0.1))
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        feed = {k: convert_feed_value(main.global_block(), k, v)
                for k, v in chip_smoke.ernie_feed(cfg, 8, 128).items()}
        names = sorted(v.name for v in main.list_vars()
                       if v.persistable and scope.has_var(v.name))
        step = cp._build(sorted(feed), [loss.name], names, names,
                         {k: v.ndim for k, v in feed.items()})
        return step.trace(
            {n: scope.find_var(n) for n in names}, feed,
            jax.random.key(0)).lower(lowering_platforms=("tpu",)).as_text()
    finally:
        mp.undo()


def test_data_parallel_flash_attention_lowers_for_tpu(dp_step_text):
    """Under a mesh the lowering refuses a bare Mosaic call; the
    flash_attention op runs it per data shard inside a shard_map."""
    assert "tpu_custom_call" in dp_step_text


def test_data_parallel_labelled_rows_head_lowers_for_tpu(dp_step_text):
    """The fused masked-LM head runs per data shard too: its loops of
    dynamic length (forward and backward) sit inside a shard_map, each shard
    compacting its own 128 positions, and no logits of all positions
    exist."""
    text = dp_step_text
    assert text.count("stablehlo.while") >= 2
    assert "sdy.manual_computation" in text or "shard_map" in text
    assert "1024x100x" not in text and "8x128x100x" not in text


def test_data_parallel_ssd_scan_lowers_for_tpu():
    """A Mamba-2 block under a data-parallel mesh: the scan's two kernels
    run per data shard inside a shard_map (GSPMD cannot partition a Mosaic
    call), forward, recomputed forward and backward."""
    from paddle_tpu.core.executor import convert_feed_value
    from paddle_tpu.models import nemotron_h as nh
    from paddle_tpu.observability import get_registry

    def lowered():
        return sum(s["value"] for s in get_registry().series()
                   if s["name"] == "ops/ssd_scan_lowered"
                   and s["labels"].get("path") == "pallas")

    cfg = nh.NemotronHConfig(
        vocab_size=64, hidden_size=32, pattern="M", mamba_num_heads=4,
        mamba_head_dim=64, ssm_state_size=128, n_groups=2, chunk_size=128)
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = nh.build_pretrain_program(
            cfg, 8, 256, lambda: fluid.optimizer.SGD(0.1))
    cp = fluid.CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    ids = np.zeros((8, 256), "int32")
    feed = {k: convert_feed_value(main.global_block(), k, v)
            for k, v in {"ids": ids, "labels": ids[:, :, None]}.items()}
    names = sorted(v.name for v in main.list_vars()
                   if v.persistable and scope.has_var(v.name))
    step = cp._build(sorted(feed), [loss.name], names, names,
                     {k: v.ndim for k, v in feed.items()})
    before = lowered()
    text = step.trace({n: scope.find_var(n) for n in names}, feed,
                      jax.random.key(0)).lower(
                          lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "sdy.manual_computation" in text or "shard_map" in text
    assert lowered() > before


@pytest.mark.parametrize(
    "n, h, v, x_dtype, bias, chunks",
    [(64 * 512, 768, 30522, jnp.bfloat16, True, (1024, 1024)),
     (4 * 2 * 4096, 2048, 49152, jnp.float32, False, (512, 1024))],
    ids=["ernie_base", "ouro_2_6b"])
def test_labelled_rows_head_compiles_for_v5e(one_chip, n, h, v, x_dtype,
                                             bias, chunks):
    """The head of `ernie_base.seq512` (64 x 512 positions, bf16
    activations, the float32 768 x 30,522 matrix) and of `ouro_2_6b.train4k`
    (four exits of 2 x 4,096 positions in float32, 2,048 x 49,152, no bias),
    loss and gradients, through the TPU's own compiler with the rows the
    rule gives: two loops of dynamic length, no [positions, vocab] value,
    the step's temporaries far under what bf16 logits of all positions would
    take, and the accumulator of dW read and written at most once for every
    1,024 rows."""
    from paddle_tpu.ops import nn_ops

    assert nn_ops.linear_ce_chunk_rows(n, v) == chunks

    def loss(x, w, b, lbl):
        return jnp.sum(nn_ops._linear_ce(x, w, b if bias else None, lbl,
                                         -100, chunks))

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((n, h), x_dtype),
                                 ((h, v), jnp.float32), ((v,), jnp.float32),
                                 ((n,), jnp.int32))]
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2) if bias else (0, 1))
                       ).trace(*args).lower(
                           lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 2
    assert f"[{n},{v}]" not in text and f"[{v},{n}]" not in text
    assert f"f32[{h},{v}]" in text and f"[{chunks[1]},{v}]" in text
    dense_logits = n * v * 2
    assert compiled.memory_analysis().temp_size_in_bytes < dense_logits / 2


def test_gqa_flash_attention_compiles_for_v5e_at_nemotron_width(one_chip):
    """The attention block of `nemotron3_nano.train8k`: 32 query heads on 2
    key/value heads of 128, causal, T 8,192, bf16, forward and backward,
    through the TPU's own compiler (Mosaic's tiling and VMEM limits): two
    kernels (the forward, one backward with a head's dq in VMEM), k and v
    never copied per query head, dk and dv summed over the
    group to the two heads they belong to."""
    b, t, hq, hkv, d = 2, 8192, 32, 2, 128

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_packed(
            q, k, v, hq, causal=True, num_kv_heads=hkv).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((b, t, n * d), jnp.bfloat16,
                                 sharding=one_chip) for n in (hq, hkv, hkv)]
    # the suite asks for float32-exact products; the chip's kernels take
    # their bf16 operands as they are
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    dq, dk, dv = jax.eval_shape(jax.grad(loss, (0, 1, 2)), *args)
    assert dk.shape == dv.shape == (b, t, hkv * d) and dq.shape[2] == hq * d
    # nothing the size of k or v repeated for all 32 query heads is an
    # operand of a kernel: the kernels' k/v operands are [B*2, T, 128]
    assert f"bf16[{b * hkv},{t},{d}]" in text


def _scan_structs(one_chip, b, t, h, p, g, n, dtype):
    return [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in (((b, t, h, p), dtype),
                              ((b, t, h), jnp.float32), ((h,), jnp.float32),
                              ((b, t, g, n), dtype), ((b, t, g, n), dtype),
                              ((h,), jnp.float32))]


def test_ssd_scan_kernels_compile_for_v5e_at_nemotron_width(one_chip):
    """A mixer's scan of `nemotron3_nano.train8k` (b2 x T8192, 64 heads of
    64 in 8 groups, state 128, chunks of 128, bf16), forward and backward,
    through the TPU's own compiler: two kernels, and no temporary of the
    size of the per-head [chunk, chunk] decays the einsum form writes (537
    MB, float32, three times over): the largest is the chunks' entering
    states (268 MB)."""
    b, t, h, p, g, n = 2, 8192, 64, 64, 8, 128
    assert ssd.takes(t, h, p, g, n, 128)

    def loss(*z):
        return jnp.sum(ssd.ssd_scan(*z, 128).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, tuple(range(6)))).trace(
        *_scan_structs(one_chip, b, t, h, p, g, n, jnp.bfloat16)).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert f"f32[{b},{t // 128},{g},{h // g},128,128]" not in text
    decays = b * (t // 128) * h * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * decays


@pytest.mark.parametrize("h,p,g,n,chunk,dtype", [
    (1, 128, 1, 128, 128, "float32"), (8, 32, 2, 128, 128, "bfloat16"),
    (32, 8, 2, 128, 128, "bfloat16"), (4, 64, 2, 256, 256, "bfloat16")],
    ids=["one_head_a_tile", "four_heads_a_tile", "sixteen_heads_a_tile",
         "chunk_and_state_of_256"])
def test_ssd_scan_kernels_compile_for_the_shapes_the_rule_takes(
        one_chip, h, p, g, n, chunk, dtype):
    """Mosaic accepts what `ssd_scan.supports` accepts: heads of a whole
    128-lane tile and of a fraction of it, chunks and states of 256."""
    assert ssd.supports(512, h, p, g, n, chunk)

    def loss(*z):
        return jnp.sum(ssd.ssd_scan(*z, chunk).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, tuple(range(6)))).trace(
        *_scan_structs(one_chip, 2, 512, h, p, g, n, jnp.dtype(dtype))
    ).lower(lowering_platforms=("tpu",)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "full"])
def test_remat_blocks_with_their_kept_values_compile_for_v5e(one_chip, kept):
    """A Mamba-2 block, an expert block and an attention block at the
    smallest sizes the kernels take, the whole training step under the
    builder's own remat policy, through the TPU's own compiler. With what
    the blocks keep (`Program.remat_keep`) the attention forward kernel is
    called once and the dispatch sorts once; with nothing kept, twice. The
    scan's forward kernel runs twice either way."""
    from paddle_tpu.models import nemotron_h as nh

    cfg = nh.NemotronHConfig(
        vocab_size=256, hidden_size=256, pattern="ME*", num_heads=4,
        num_kv_heads=2, head_dim=128, mamba_num_heads=4, mamba_head_dim=64,
        ssm_state_size=128, n_groups=2, chunk_size=128, n_routed_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=128,
        shared_intermediate_size=256, experts_held=(2, 4))
    b, t = 2, 512
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = nh.build_pretrain_program(
            cfg, b, t, lambda: fluid.optimizer.SGD(0.1))
    if not kept:
        main.remat_keep = {}
    state = {v.name: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype,
                                          sharding=one_chip)
             for v in startup.list_vars() if v.persistable}
    feed = {"ids": jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip),
            "labels": jax.ShapeDtypeStruct((b, t, 1), jnp.int32,
                                           sharding=one_chip)}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    names = sorted(state)
    step = fluid.Executor(fluid.TPUPlace())._build(
        main, sorted(feed), [loss.name], names, names)
    with jax.default_matmul_precision("default"):
        text = jax.jit(step._step).trace(state, feed, key).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    # the scan: forward, forward again, backward; attention: forward (and
    # again where nothing is kept), one backward; the experts' grouped
    # product: the rows laid out and the walk, forward and backward (the
    # forward made again behind the remat block has no reader: its
    # residuals are the block's own inputs)
    assert text.count("tpu_custom_call") == 3 + (2 if kept else 3) + 4
    # the router's top-k and the dispatch's argsort: a sort each to XLA
    assert text.count(" sort(") == (2 if kept else 4)


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "full"])
def test_looped_decoder_compiles_for_v5e_with_its_kept_values(one_chip, kept):
    """Two layers run twice over shared weights (`models/ouro.py`) at the
    smallest sizes the attention kernels take, the whole training step
    through the TPU's own compiler: every application keeps its kernel's
    `out` and `lse`, so the forward kernel is called once an application
    (at this length a forward and one backward kernel; with nothing kept the
    forward twice), and the step holds one master weight a layer and matrix
    whatever the number of passes."""
    from paddle_tpu.models import ouro

    cfg = ouro.OuroConfig(vocab_size=256, hidden_size=256, num_layers=2,
                          num_heads=2, head_dim=128, intermediate_size=384,
                          total_ut_steps=2)
    b, t = 2, 512
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = ouro.build_pretrain_program(
            cfg, b, t, lambda: fluid.optimizer.SGD(0.1))
    if not kept:
        main.remat_keep = {}
    state = {v.name: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype,
                                          sharding=one_chip)
             for v in startup.list_vars() if v.persistable}
    assert sum(int(np.prod(s.shape)) for n, s in state.items()
               if n.startswith(("blk", "embed", "lm_head", "final_norm",
                                "exit_gate"))) == ouro.param_count(cfg)
    feed = {"ids": jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip),
            "labels": jax.ShapeDtypeStruct((b, t, 1), jnp.int32,
                                           sharding=one_chip)}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    names = sorted(state)
    step = fluid.Executor(fluid.TPUPlace())._build(
        main, sorted(feed), [loss.name], names, names)
    with jax.default_matmul_precision("default"):
        text = jax.jit(step._step).trace(state, feed, key).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if kept else 3) * 2 * 2


def test_gqa_flash_attention_compiles_for_v5e_at_lfm2_width(one_chip):
    """An attention layer of `lfm2_24b_a2b.train8k`: 32 query heads on 8
    key/value heads of 64 (grouped heads and head size 64 together), causal,
    T 8,192, bf16, forward and backward, through the TPU's own compiler:
    two kernels, k and v at their eight heads' width."""
    b, t, hq, hkv, d = 2, 8192, 32, 8, 64

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_packed(
            q, k, v, hq, causal=True, num_kv_heads=hkv).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((b, t, n * d), jnp.bfloat16,
                                 sharding=one_chip) for n in (hq, hkv, hkv)]
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    dq, dk, dv = jax.eval_shape(jax.grad(loss, (0, 1, 2)), *args)
    assert dk.shape == dv.shape == (b, t, hkv * d) and dq.shape[2] == hq * d
    assert f"bf16[{b * hkv},{t},{d}]" in text


def test_latent_attention_kernels_compile_for_v5e_at_joyai_width(one_chip):
    """The attention call of `joyai_llm_flash.train8k`: 32 heads of 192-wide
    queries and keys on 128-wide values, causal, T 8,192, bf16, forward and
    backward, through the TPU's own compiler (a block's minor dimension of
    192 is one and a half lane tiles: Mosaic's tiling and VMEM limits say
    whether that runs): two kernels, out and dv at the values' width, dq
    (a head's [8192, 192] float32 sum in VMEM) and dk at the keys'."""
    b, t, h, d, dv = 2, 8192, 32, 192, 128

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_packed(
            q, k, v, h, causal=True).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((b, t, h * n), jnp.bfloat16,
                                 sharding=one_chip) for n in (d, d, dv)]
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).trace(
            *args).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    dq, dk, dv_ = jax.eval_shape(jax.grad(loss, (0, 1, 2)), *args)
    assert dq.shape == dk.shape == (b, t, h * d)
    assert dv_.shape == (b, t, h * dv)
    # the kernels' operands, heads folded: q and k 192 wide, v and out 128
    assert f"bf16[{b * h},{t},{d}]" in text
    assert f"bf16[{b * h},{t},{dv}]" in text


@pytest.mark.parametrize("hq,window,tiles", [(64, 512, 31), (48, None, 36)],
                         ids=["window_layer_64on8", "full_layer_48on8"])
def test_laguna_attention_kernels_compile_for_v5e(one_chip, hq, window,
                                                  tiles):
    """An attention layer of `laguna_xs2.train8k`: 64 query heads under a
    window of 512, or 48 causal, on 8 key/value heads of 128 (groups of 8 and
    of 6), T 8,192, bf16, forward and backward, through the TPU's own
    compiler: two kernels, k and v at their eight heads' width; the windowed
    call walks the band's tiles alone."""
    from paddle_tpu.observability import get_registry
    b, t, hkv, d = 2, 8192, 8, 128

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_packed(
            q, k, v, hq, causal=True, num_kv_heads=hkv,
            window=window).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((b, t, n * d), jnp.bfloat16,
                                 sharding=one_chip) for n in (hq, hkv, hkv)]
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    dq, dk, dv = jax.eval_shape(jax.grad(loss, (0, 1, 2)), *args)
    assert dk.shape == dv.shape == (b, t, hkv * d) and dq.shape[2] == hq * d
    assert f"bf16[{b * hkv},{t},{d}]" in text
    call = "window" if window else "causal"
    scheduled = {s["labels"]["kernel"]: s["value"]
                 for s in get_registry().series()
                 if s["name"] == "flash_attention/tiles_scheduled"
                 and s["labels"]["call"] == call}
    assert scheduled["fwd"] == scheduled["bwd"] == tiles


@pytest.mark.parametrize("form", ["pallas", "einsum"])
def test_the_gated_delta_rule_compiles_for_v5e_at_kimi_linear_s_width(
        one_chip, form):
    """One KDA layer's call of the chunked gated delta rule as
    `kimi_linear_48b_a3b.train8k` makes it (b2 x T8192, 32 heads of 128,
    chunks of 64, bf16 q, k, v and raw decay gate, the l2 normalisation and
    the gate's softplus inside), forward and all seven gradients, through the
    TPU's own compiler. "pallas", what the shapes pick on a TPU: the forward
    and the backward kernel of ops/pallas_kernels/kda_chunk.py and no loop,
    the state entering each tile of 128 positions (256 MiB) all that is kept
    beside the inputs, under 1 GiB of temporaries. "einsum", what the op
    takes under a mesh: XLA loops and no Mosaic call; beside the states
    entering the chunks (512 MiB) and the inputs and cotangents laid out
    heads first (bf16, 128 MiB each) nothing of a whole layer's size, and
    nothing of the size of the float32 log-decay, which a group of chunks
    makes and drops: under 2.3 GiB of temporaries (1.94 read here; the
    chunk-local terms of a whole layer at once took 7.7)."""
    from paddle_tpu.ops import linear_attn_ops as la
    b, t, h, k = 2, 8192, 32, 128
    wide = jax.ShapeDtypeStruct((b, t, h, k), jnp.bfloat16, sharding=one_chip)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)

    def loss(q, kk, v, g, beta, a_log, dt_bias):
        out, _ = la.kda_rule(q, kk, v, g, beta, (a_log, dt_bias), 64,
                             k ** -0.5, 1e-6, under_mesh=form == "einsum")
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, tuple(range(7)))).trace(
        wide, wide, wide, wide, f32(b, t, h), f32(h), f32(h, k)).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert f"f32[{b},{t},{h},{k}]" not in text
    if form == "pallas":
        assert text.count("tpu_custom_call") == 2 and " while(" not in text
        assert f"f32[{b},{h},{t // 128},{k},{k}]" in text
        assert temp < 2 ** 30, temp / 2 ** 30
        return
    assert "tpu_custom_call" not in text and text.count(" while(") >= 4
    groups = la._groups(t, b, h, 64)
    assert groups == 16
    assert f"f32[{groups},{t // 64 // groups},{b},{h},{k},{k}]" in text
    assert temp < 2.3 * 2 ** 30, temp / 2 ** 30
