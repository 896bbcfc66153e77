"""linear_softmax_with_cross_entropy: the masked-LM head on the labelled
positions only (ops/nn_ops.py `_linear_ce`), against the dense pair it
replaces, `fc` -> `softmax_with_cross_entropy(ignore_index)`.

The op must be exact for every label count: the loss and the gradients of
X, W and Bias are the pair's numbers, the ignored rows of dX are exactly 0,
and the rows it projects are whole chunks of the labelled count.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.contrib.mixed_precision.fp16_lists import (
    AutoMixedPrecisionLists)
from paddle_tpu.models import bert
from paddle_tpu.observability import get_registry
from paddle_tpu.ops import nn_ops

B, T, H, V = 4, 16, 32, 64
CHUNK = 8
N = B * T


def _shrink_chunks(monkeypatch, vocab):
    """Forward chunks of CHUNK rows and backward chunks of two of them at
    this vocabulary (the rows are derived from the shapes; at these sizes
    one chunk would cover all positions)."""
    monkeypatch.setattr(nn_ops, "_CE_CHUNK_BYTES", 4 * vocab * CHUNK)
    assert nn_ops.linear_ce_chunk_rows(N, vocab) == (CHUNK, 2 * CHUNK)


@pytest.fixture()
def small_chunks(monkeypatch):
    _shrink_chunks(monkeypatch, V)


def _labels(count, rng, vocab=V):
    lab = np.full(N, -100, "int64")
    at = rng.permutation(N)[:count]
    lab[at] = rng.randint(0, vocab, count)
    return lab.reshape(B, T, 1)


def _head_grads(fused, amp, lab, rng, bias=True, vocab=V, remat=None,
                text=None):
    """Loss (weighted by a seeded cotangent) and d/dX, d/dW, d/dBias of one
    head, fused or dense, float32 or bf16-AMP, with or without a bias.
    `remat`: "policy" sets `Program.remat_policy = "full"`; "strategy" and
    "strategy_off" run over two data shards with `BuildStrategy.remat` True
    and False. `text` (a list) receives the compiled step's text."""
    import jax

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", [T, H], dtype="float32")
        x.stop_gradient = False
        lbl = layers.data("lbl", [T, 1], dtype="int64")
        cot = layers.data("cot", [T, 1], dtype="float32")
        h = layers.cast(x, "bfloat16") if amp else x
        w_attr = fluid.ParamAttr(name="w")
        b_attr = fluid.ParamAttr(name="b") if bias else False
        extra = []
        if fused:
            loss, rows, n = layers.linear_softmax_with_cross_entropy(
                h, lbl, vocab, param_attr=w_attr, bias_attr=b_attr,
                return_rows=True)
            extra = [rows, n]
        else:
            logits = layers.fc(h, vocab, num_flatten_dims=2,
                               param_attr=w_attr, bias_attr=b_attr)
            loss = layers.softmax_with_cross_entropy(logits, lbl,
                                                     ignore_index=-100)
        total = layers.reduce_sum(layers.elementwise_mul(loss, cot))
        params = [main.global_block().var(n) for n in ("wb" if bias else "w")]
        grads = fluid.gradients([total], [x, *params])
    if amp:
        lists = AutoMixedPrecisionLists()
        main._amp = {"dtype": "bfloat16", "white_list": lists.white_list,
                     "black_list": lists.black_list}
    feed = {"x": rng.randn(B, T, H).astype("float32"), "lbl": lab,
            "cot": rng.rand(B, T, 1).astype("float32")}
    prog = main
    if remat == "policy":
        main.remat_policy = "full"
    elif remat is not None:
        strategy = fluid.BuildStrategy()
        strategy.remat = remat == "strategy"
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=total.name, build_strategy=strategy,
            places=jax.devices()[:2])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set_var("w", (rng.randn(H, vocab) * 0.3).astype("float32"))
        b = (rng.randn(vocab) * 0.1).astype("float32")  # drawn either way
        if bias:
            scope.set_var("b", b)
        out = exe.run(prog, feed=feed, fetch_list=[loss, *grads, *extra])
        if text is not None:
            text.append(exe.compiled_step(prog).as_text())
    return [np.asarray(o, "float32") for o in out]


_COUNTS = {"none": 0, "one": 1, "chunk-1": CHUNK - 1, "chunk": CHUNK,
           "chunk+1": CHUNK + 1, "15pct": round(0.15 * N), "all": N}
# (labelled count, bias, vocabulary): every count at the narrow vocabulary
# with a bias; a head without a bias (both decoders'); every row labelled at
# a vocabulary eight times as wide (Ouro's case: the backward's rows are not
# the forward's)
_HEADS = {**{k: (c, True, V) for k, c in _COUNTS.items()},
          "15pct_no_bias": (_COUNTS["15pct"], False, V),
          "all_no_bias": (N, False, V),
          "all_wide_vocab": (N, True, 8 * V),
          "all_wide_vocab_no_bias": (N, False, 8 * V)}


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
@pytest.mark.parametrize("head", list(_HEADS))
def test_fused_head_equals_dense_pair(monkeypatch, head, amp):
    count, bias, vocab = _HEADS[head]
    _shrink_chunks(monkeypatch, vocab)
    lab = _labels(count, np.random.RandomState(count), vocab)
    got = _head_grads(True, amp, lab, np.random.RandomState(7), bias, vocab)
    ref = _head_grads(False, amp, lab, np.random.RandomState(7), bias, vocab)
    # bf16: the pair rounds its logits to bf16 before the softmax, the fused
    # op keeps them in float32; both multiply bf16 operands
    tol = 3e-2 if amp else 1e-5
    names = ("loss", "dX", "dW", "dBias") if bias else ("loss", "dX", "dW")
    for name, a, r in zip(names, got, ref):
        assert a.shape == r.shape, name
        np.testing.assert_allclose(a, r, rtol=tol,
                                   atol=tol * max(1e-6, np.abs(r).max()),
                                   err_msg=name)
    ignored = lab.reshape(N) == -100
    loss, dx = got[0].reshape(N), got[1].reshape(N, H)
    assert not loss[ignored].any() and not dx[ignored].any()
    assert count == 0 or np.abs(dx[~ignored]).min(axis=1).max() > 0
    rows, labelled = (int(v) for v in got[-2:])
    assert labelled == count
    assert rows == -(-count // CHUNK) * CHUNK


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("remat, plain", [("policy", None),
                                          ("strategy", "strategy_off")],
                         ids=["remat_policy_full", "build_strategy_remat"])
def test_head_under_a_remat_policy_is_not_wrapped_again(small_chunks, remat,
                                                        plain, bias):
    """The op is its own rematerialisation (`own_remat` in its registration):
    under a policy that checkpoints every op outside a remat block the step
    still holds the head's two loops, forward and backward, not a third (the
    forward rule made again), the skipped wrap is counted, and the loss and
    every gradient are the unwrapped op's bit for bit."""
    def own():
        snap = get_registry().snapshot(deep=False)
        return sum(v for k, v in snap.items()
                   if k.startswith("remat/op_own")
                   and "linear_softmax_with_cross_entropy" in k)

    lab = _labels(N, np.random.RandomState(1))
    texts = []
    ref = _head_grads(True, False, lab, np.random.RandomState(7), bias,
                      remat=plain, text=texts)
    before = own()
    got = _head_grads(True, False, lab, np.random.RandomState(7), bias,
                      remat=remat, text=texts)
    assert [t.count(" while(") for t in texts] == [2, 2]
    assert own() > before
    for name, a, r in zip(("loss", "dX", "dW", "dBias"), got, ref):
        np.testing.assert_array_equal(a, r, err_msg=name)


@pytest.mark.parametrize(
    "n_pos, vocab, want",
    [(64 * 512, 30522, (1024, 1024)),
     (256 * 128, 30522, (1024, 1024)),
     (64 * 512, 30522, (1024, 1024)),            # a data shard of dp4's 256
     (2 * 8192, 16384, (2048, 2048)),
     (4 * 2 * 4096, 49152, (512, 1024)),
     (2 * 128, 30522, (256, 256)),
     (100, 64, (104, 104)),
     (1000, 49152, (512, 512)),
     (32768, 1 << 20, (32, 64))],
    ids=["ernie_base.seq512", "ernie_base.seq128", "ernie_base.dp4_seq512",
         "nemotron3_nano.train8k", "ouro_2_6b.train4k", "fewer_positions",
         "sublane_multiple", "whole_forward_chunks", "widest_vocabulary"])
def test_chunk_rows_follow_the_shapes(n_pos, vocab, want):
    """The five token cells' heads and the rule's edges. Forward: the float32
    logits of a chunk stay on the chip (125 MB at BERT's 1,024 rows).
    Backward: whole forward chunks, at or over the ridge of the dW
    accumulator's traffic (rows / 4 operations a byte against 240, whatever
    the hidden width: ERNIE's 768, Nemotron's 2,688, Ouro's 2,048) wherever
    the positions and the softmax gradient's bytes allow: BERT's and
    Nemotron's rows are the forward's, as before the backward had rows of
    its own; Ouro's are twice the forward's. Never more rows than positions,
    always a sublane multiple."""
    fwd, bwd = nn_ops.linear_ce_chunk_rows(n_pos, vocab)
    assert (fwd, bwd) == want
    assert fwd % 8 == 0 and bwd % fwd == 0 and bwd <= -(-n_pos // 8) * 8
    assert 4 * fwd * vocab <= nn_ops._CE_CHUNK_BYTES or fwd == 8
    ridge = nn_ops._CE_RIDGE_ROWS
    assert ridge / 4 >= 240 > ridge / 8        # a v5e: 197 TFLOP/s, 819 GB/s
    if n_pos >= ridge and 2 * ridge * vocab <= nn_ops._CE_CHUNK_BYTES:
        assert bwd >= ridge


def _cfg(tp_axis=None):
    return bert.BertConfig(vocab_size=V, hidden_size=H, num_layers=1,
                           num_heads=2, ffn_size=64, max_position=T,
                           hidden_dropout=0.0, attn_dropout=0.0,
                           use_flash_attention=False, tp_axis=tp_axis)


def _pretrain(tp_axis, data_parallel, steps=3):
    """`steps` Adam steps of the pretraining program on a batch of 8:
    losses, the first step's head gradients, the parameters after."""
    import jax

    with fluid.unique_name.guard():
        main, startup, _, loss = bert.build_pretrain_program(
            _cfg(tp_axis), 8, T,
            optimizer_factory=lambda: fluid.optimizer.Adam(1e-2))
    main.random_seed = startup.random_seed = 5
    rng = np.random.RandomState(3)
    lab = np.where(rng.rand(8, T, 1) < 0.3, rng.randint(0, V, (8, T, 1)),
                   -100).astype("int64")
    feed = {"src_ids": rng.randint(0, V, (8, T)).astype("int64"),
            "pos_ids": np.tile(np.arange(T), (8, 1)).astype("int64"),
            "sent_ids": np.zeros((8, T), "int64"),
            "input_mask": np.ones((8, T), "float32"), "mlm_labels": lab}
    prog = main
    if data_parallel:
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=jax.devices()[:4])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fetch = [loss, "mlm_out.w@GRAD", "mlm_out.b@GRAD"]
        first = [np.asarray(v) for v in exe.run(prog, feed=feed,
                                                fetch_list=fetch)]
        losses = [float(first[0])] + [
            float(exe.run(prog, feed=feed, fetch_list=[loss])[0])
            for _ in range(steps - 1)]
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.all_parameters()}
    return main, losses, first[1:], params


@pytest.mark.parametrize("data_parallel", [False, True],
                         ids=["one_device", "dp4"])
def test_pretrain_step_equals_dense_head(small_chunks, data_parallel):
    """The whole program with the fused head, on one device and per data
    shard on four, against the dense pair on one device: same losses, same
    head gradients (summed over the shards once) and same parameters after
    three Adam steps."""
    dense_main, ref_losses, ref_grads, ref_params = _pretrain("tp", False)
    main, losses, grads, params = _pretrain(None, data_parallel)
    types = [op.type for op in main.global_block().ops]
    assert "linear_softmax_with_cross_entropy" in types
    assert "softmax_with_cross_entropy" not in types
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-6)
    assert sorted(params) == sorted(ref_params)
    for name, r in ref_params.items():
        np.testing.assert_allclose(params[name], r, rtol=1e-3, atol=2e-5,
                                   err_msg=name)
    snap = get_registry().snapshot(deep=False)
    path = "per_data_shard" if data_parallel else "whole"
    assert any(k.startswith("ops/linear_ce_lowered") and path in k
               for k in snap), sorted(k for k in snap if "linear_ce" in k)


def test_tensor_parallel_program_keeps_the_dense_pair():
    """A vocabulary-sharded output matrix takes the dense path: chosen by
    the weight's shard spec at build time, which tensor_parallel.py's
    structural derivation recognises by the pair."""
    from paddle_tpu.parallel import derive_tp_specs

    with fluid.unique_name.guard():
        main, _, _, _ = bert.build_pretrain_program(_cfg("tp"), 2, T)
    types = [op.type for op in main.global_block().ops]
    assert "linear_softmax_with_cross_entropy" not in types
    head = types.index("softmax_with_cross_entropy")
    assert types[head - 2:head] == ["mul", "elementwise_add"]
    w = main.global_block().var("mlm_out.w")
    assert tuple(w.shard_spec) == (None, "tp")
    specs = derive_tp_specs(main, min_embed_rows=32, min_matmul_dim=32)
    assert tuple(specs["mlm_out.w"]) == (None, "tp")
    built = [k for k in get_registry().snapshot(deep=False)
             if k.startswith("models/bert_head_built")]
    assert any("dense" in k for k in built), built
