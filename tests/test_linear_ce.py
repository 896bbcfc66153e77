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


@pytest.fixture()
def small_chunks(monkeypatch):
    """Chunks of CHUNK rows at this vocabulary (the size is derived from the
    shapes; at V = 64 it would cover all positions in one)."""
    monkeypatch.setattr(nn_ops, "_CE_CHUNK_LOGITS_BYTES", 4 * V * CHUNK)
    assert nn_ops.linear_ce_chunk_rows(N, V) == CHUNK


def _labels(count, rng):
    lab = np.full(N, -100, "int64")
    at = rng.permutation(N)[:count]
    lab[at] = rng.randint(0, V, count)
    return lab.reshape(B, T, 1)


def _head_grads(fused, amp, lab, rng):
    """Loss (weighted by a seeded cotangent) and d/dX, d/dW, d/dBias of one
    head, fused or dense, float32 or bf16-AMP."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", [T, H], dtype="float32")
        x.stop_gradient = False
        lbl = layers.data("lbl", [T, 1], dtype="int64")
        cot = layers.data("cot", [T, 1], dtype="float32")
        h = layers.cast(x, "bfloat16") if amp else x
        w_attr, b_attr = fluid.ParamAttr(name="w"), fluid.ParamAttr(name="b")
        extra = []
        if fused:
            loss, rows, n = layers.linear_softmax_with_cross_entropy(
                h, lbl, V, param_attr=w_attr, bias_attr=b_attr,
                return_rows=True)
            extra = [rows, n]
        else:
            logits = layers.fc(h, V, num_flatten_dims=2, param_attr=w_attr,
                               bias_attr=b_attr)
            loss = layers.softmax_with_cross_entropy(logits, lbl,
                                                     ignore_index=-100)
        total = layers.reduce_sum(layers.elementwise_mul(loss, cot))
        w, b = (main.global_block().var(n) for n in "wb")
        grads = fluid.gradients([total], [x, w, b])
    if amp:
        lists = AutoMixedPrecisionLists()
        main._amp = {"dtype": "bfloat16", "white_list": lists.white_list,
                     "black_list": lists.black_list}
    feed = {"x": rng.randn(B, T, H).astype("float32"), "lbl": lab,
            "cot": rng.rand(B, T, 1).astype("float32")}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        scope.set_var("w", (rng.randn(H, V) * 0.3).astype("float32"))
        scope.set_var("b", (rng.randn(V) * 0.1).astype("float32"))
        out = exe.run(main, feed=feed, fetch_list=[loss, *grads, *extra])
    return [np.asarray(o, "float32") for o in out]


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
@pytest.mark.parametrize(
    "count", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1, round(0.15 * N), N],
    ids=["none", "one", "chunk-1", "chunk", "chunk+1", "15pct", "all"])
def test_fused_head_equals_dense_pair(small_chunks, count, amp):
    lab = _labels(count, np.random.RandomState(count))
    got = _head_grads(True, amp, lab, np.random.RandomState(7))
    ref = _head_grads(False, amp, lab, np.random.RandomState(7))
    # bf16: the pair rounds its logits to bf16 before the softmax, the fused
    # op keeps them in float32; both multiply bf16 operands
    tol = 3e-2 if amp else 1e-5
    for name, a, r in zip(("loss", "dX", "dW", "dBias"), got, ref):
        assert a.shape == r.shape, name
        np.testing.assert_allclose(a, r, rtol=tol,
                                   atol=tol * max(1e-6, np.abs(r).max()),
                                   err_msg=name)
    ignored = lab.reshape(N) == -100
    loss, dx = got[0].reshape(N), got[1].reshape(N, H)
    assert not loss[ignored].any() and not dx[ignored].any()
    assert count == 0 or np.abs(dx[~ignored]).min(axis=1).max() > 0
    rows, labelled = int(got[4]), int(got[5])
    assert labelled == count
    assert rows == -(-count // CHUNK) * CHUNK


def test_chunk_rows_follow_the_shapes():
    """1,024 rows at BERT's vocabulary (125 MB of float32 logits), never
    more than the positions there are, always a sublane multiple."""
    assert nn_ops.linear_ce_chunk_rows(64 * 512, 30522) == 1024
    assert nn_ops.linear_ce_chunk_rows(16 * 512, 30522) == 1024
    assert nn_ops.linear_ce_chunk_rows(2 * 128, 30522) == 256
    assert nn_ops.linear_ce_chunk_rows(100, 64) == 104
    assert nn_ops.linear_ce_chunk_rows(32768, 1 << 20) == 32


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
