"""The names in the compiled step (observability/scopes.py): what the
lowering writes from the Program IR, what `op_scopes` reads back from the
compiled text on both paths, and the compile cache that must not hand a
scoped step an unscoped executable."""
import contextlib
import re
from collections import Counter

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import bert, deepfm
from paddle_tpu.observability import scopes

LAYERS = 2
# instructions that only carry values around: XLA gives them no op_name
_PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element")


def _opcode(scope):
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + scope.text.split(" = ", 1)[1])
    return m.group(1) if m else "?"


def _bert(batch=4, seq=16):
    cfg = bert.BertConfig(vocab_size=128, hidden_size=32, num_layers=LAYERS,
                          num_heads=2, ffn_size=64, max_position=32)
    main, startup, _, loss = bert.build_pretrain_program(
        cfg, batch, seq, optimizer_factory=lambda: fluid.optimizer.Adam(1e-3))
    rng = np.random.RandomState(0)
    feed = {"src_ids": rng.randint(0, 128, (batch, seq)).astype("int64"),
            "pos_ids": np.tile(np.arange(seq), (batch, 1)).astype("int64"),
            "sent_ids": np.zeros((batch, seq), "int64"),
            "input_mask": np.ones((batch, seq), "float32"),
            "mlm_labels": rng.randint(0, 128, (batch, seq, 1)).astype("int64")}
    return main, startup, loss, feed


def _run(program, startup, loss, feed, steps=2):
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    for _ in range(steps):
        exe.run(program, feed=feed, fetch_list=[loss], scope=scope)
    return exe


@pytest.fixture(scope="module")
def bert_scopes():
    with fluid.unique_name.guard():
        main, startup, loss, feed = _bert()
    exe = _run(main, startup, loss, feed)
    return exe, main, scopes.op_scopes(exe.compiled_step(main))


def test_every_dot_names_its_layer_and_its_phase(bert_scopes):
    _, _, found = bert_scopes
    dots = [s for s in found.values() if _opcode(s) in ("dot", "convolution")]
    assert dots and all(s.has_dot for s in dots)
    by_unit = Counter((s.unit, s.phase) for s in dots)
    for i in range(LAYERS):
        # forward: qkv, attention out, two of the feed-forward net, and the
        # attention's own two; backward matmuls keep their layer's name
        assert by_unit[(f"bert_layer_{i}", "fwd")] >= 4
        assert by_unit[(f"bert_layer_{i}", "bwd")] >= 8
    # the head is one op with a loop in each pass: the chunk's logits
    # forward; dX and dW backward, and the logits again unless XLA finds the
    # forward's (here one chunk covers every position)
    assert by_unit[("mlm_head", "fwd")] == 1
    assert by_unit[("mlm_head", "bwd")] in (2, 3)
    assert {u for u, _ in by_unit} == {"mlm_head"} | {
        f"bert_layer_{i}" for i in range(LAYERS)}
    assert all({"mul", "flash_attention",
                "linear_softmax_with_cross_entropy"} & set(s.op_types)
               for s in dots)
    head = [s for s in dots if s.unit == "mlm_head"]
    assert all(s.op_types == ("linear_softmax_with_cross_entropy",)
               for s in head)


def test_all_three_phases_occur_and_most_instructions_resolve(bert_scopes):
    _, _, found = bert_scopes
    phases = Counter(s.phase for s in found.values())
    assert phases["fwd"] and phases["bwd"] and phases["opt"]
    assert set(phases) <= set(scopes.PHASES)
    real = [s for s in found.values() if _opcode(s) not in _PLUMBING]
    resolved = sum(s.phase != "none" for s in real)
    assert resolved >= 0.9 * len(real), (resolved, len(real))
    units = {s.unit for s in found.values() if s.unit}
    assert {"embed", "mlm_head", "loss"} <= units
    # the optimizer's ops are the ones lowered after the autodiff op
    opt_types = {t for s in found.values() if s.phase == "opt"
                 for t in s.op_types}
    assert "adam" in opt_types and "mul" not in opt_types


def test_the_executor_hands_out_its_compiled_step(bert_scopes):
    exe, main, _ = bert_scopes
    compiled = exe.compiled_step(main)
    assert compiled.memory_analysis() is not None
    assert compiled is exe.compiled_step(main)
    # the training step was dispatched twice, the startup program once
    assert scopes.hottest_step() is not None
    with pytest.raises(RuntimeError, match="has not run"):
        fluid.Executor().compiled_step(main)


def test_a_unit_that_is_only_a_name_is_not_a_remat_unit():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data("x", [4])
        with fluid.unit("outer"):
            with fluid.remat_unit("block"):
                h = fluid.layers.fc(x, 4, act="relu")
            with fluid.unit("inner"):
                h = fluid.layers.fc(h, 4)
        fluid.layers.fc(h, 1)
    from paddle_tpu.core.program import remat_unit_of
    tags = [(op.attrs.get("__unit__"), remat_unit_of(op))
            for op in main.global_block().ops]
    assert ("outer/block", "outer/block") in tags
    assert ("outer/inner", None) in tags
    assert tags[-1] == (None, None)


def test_the_mesh_path_writes_the_same_names(bert_scopes):
    _, _, plain = bert_scopes
    with fluid.unique_name.guard():
        main, startup, loss, feed = _bert()
    program = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=jax.devices()[:4])
    exe = _run(program, startup, loss, feed)
    mesh = scopes.op_scopes(exe.compiled_step(program))

    def names(found):
        return {(s.unit, s.phase) for s in found.values()
                if s.unit and s.phase in ("fwd", "bwd", "opt")}

    assert names(mesh) == names(plain)
    assert any(s.has_dot and s.phase == "bwd" and s.unit == "bert_layer_1"
               for s in mesh.values())


def test_the_sort_and_merge_of_duplicate_ids_is_rows_merge():
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = deepfm.build_train_program(
            vocab_size=4096, num_fields=4, num_dense=3, embed_dim=4,
            is_sparse=True, fused_table=True, embedding_optimizer="adagrad",
            packed_rows={"rows_per_step": 32}, hidden_sizes=(16, 16))
    rng = np.random.RandomState(0)
    feed = {"sparse_ids": rng.randint(0, 4096, (8, 4)).astype("int64"),
            "dense": rng.rand(8, 3).astype("float32"),
            "label": rng.randint(0, 2, (8, 1)).astype("float32")}
    exe = _run(main, startup, loss, feed)
    found = scopes.op_scopes(exe.compiled_step(main))
    sorts = [s for s in found.values() if _opcode(s) == "sort"]
    assert sorts and all(s.unit == "rows/merge" for s in sorts)
    assert all("adagrad_row_packed" in s.op_types for s in sorts)
    units = {s.unit for s in found.values()}
    assert {"rows", "rows/merge", "dense"} <= units
    # the dense net's Adam and the table's Adagrad are both the optimizer's
    opt = {(s.unit, t) for s in found.values() if s.phase == "opt"
           for t in s.op_types}
    assert ("dense", "adam") in opt
    assert any(t == "adagrad_row_packed" for _, t in opt)


def test_an_unscoped_executable_in_the_cache_is_not_handed_to_a_scoped_step(
        tmp_path, monkeypatch):
    """jax strips locations from the persistent cache's key, and name scopes
    live in locations. Fill a cache from a step lowered without scopes and
    under the plain name (what the parent commit leaves on a machine), then
    compile the same program with them against that cache: the scope scheme
    is in the step's name, and the name is hashed."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def build():
        with fluid.unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data("x", [8])
                y = fluid.layers.data("y", [1])
                with fluid.unit("tower"):
                    h = fluid.layers.fc(x, 8, act="relu")
                loss = fluid.layers.mean(fluid.layers.square(
                    fluid.layers.fc(h, 1) - y))
                fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    feed = {"x": np.ones((4, 8), "float32"), "y": np.ones((4, 1), "float32")}
    old_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(scopes, "op_scope",
                      lambda *a, **k: contextlib.nullcontext())
            m.setattr(scopes, "unit_scope",
                      lambda *a, **k: contextlib.nullcontext())
            m.setattr(scopes, "autodiff_scope", contextlib.nullcontext)
            m.setattr(scopes, "scheme_name", lambda base, program: base)
            main, startup, loss = build()
            exe = _run(main, startup, loss, feed)
            bare = scopes.op_scopes(exe.compiled_step(main))
        # jax's own `transpose(jvp())` is all such a step has to say
        assert {s.phase for s in bare.values()} <= {"none", "bwd"}
        assert not any(s.unit or s.op_types for s in bare.values())
        entries = len(list(tmp_path.glob("*-cache")))
        assert entries > 0
        main, startup, loss = build()
        exe = _run(main, startup, loss, feed)
        found = scopes.op_scopes(exe.compiled_step(main))
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        cc.reset_cache()
    phases = Counter(s.phase for s in found.values())
    assert phases["fwd"] and phases["bwd"] and phases["opt"], phases
    assert any(s.unit == "tower" and s.has_dot for s in found.values())
    # the scoped step is an entry of its own beside the unscoped one
    assert len(list(tmp_path.glob("*-cache"))) > entries


def test_the_scope_scheme_is_in_the_step_s_name():
    def build(unit):
        with fluid.unique_name.guard():
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                x = fluid.layers.data("x", [4])
                with fluid.unit(unit):
                    fluid.layers.fc(x, 4)
        return main

    a, again, b = build("tower"), build("tower"), build("head")
    assert scopes.scheme_name("step", a) == scopes.scheme_name("step", again)
    assert scopes.scheme_name("step", a) != scopes.scheme_name("step", b)
    assert re.fullmatch(r"step_[0-9a-f]{8}", scopes.scheme_name("step", a))
    # so is the scheme's version: what op implementations write of their own
    # (`unit_scope`) is not in the Program
    name = scopes.scheme_name("step", a)
    scopes._SCHEME += 1
    try:
        assert scopes.scheme_name("step", a) != name
    finally:
        scopes._SCHEME -= 1


def test_a_fusion_of_an_update_with_other_work_is_mixed():
    text = '''HloModule m

%fused_computation (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/autodiff/transpose(jvp(u.bert_layer_1/op.mul))/dot_general"}
  %mul.8 = f32[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/autodiff/transpose(jvp(u.bert_layer_0/op.layer_norm))/mul"}
  %mul.9 = f32[8,8]{1,0} multiply(%mul.8, %p0), metadata={op_name="jit(step)/autodiff/transpose(jvp(u.bert_layer_0/op.layer_norm))/mul"}
  ROOT %sub.1 = f32[8,8]{1,0} subtract(%p1, %dot.1), metadata={op_name="jit(step)/opt/op.adam/sub"}
}

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  %mul.3 = f32[8,8]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="jit(step)/jvp(u.bert_layer_0/op.gelu)/mul"}
  ROOT %mul.4 = f32[8,8]{1,0} multiply(%mul.3, %p0.1), metadata={op_name="jit(step)/autodiff/transpose(jvp(u.bert_layer_0/op.gelu))/mul"}
}

ENTRY %main (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %copy.1 = f32[8,8]{1,0} copy(%a)
  %fusion.2 = f32[8,8]{1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1
  %rows = f32[8,8]{1,0} sort(%b), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(step)/opt/u.rows/op.adagrad_row_packed/u.merge/jit(argsort)/while/body/sort"}
  ROOT %fusion.1 = f32[8,8]{1,0} fusion(%fusion.2, %rows), kind=kOutput, calls=%fused_computation
}
'''
    # the text describes itself: no word from the process that compiled it
    found = scopes.op_scopes(text)
    head = found["fusion.1"]
    # the matrix product names the kernel's unit, however many small
    # operations of another unit are fused around it
    assert (head.phase, head.unit, head.has_dot) == ("mixed", "bert_layer_1",
                                                     True)
    assert head.op_types == ("adam", "layer_norm", "mul")
    # forward-named work fused into a backward kernel is spent in backward
    gelu = found["fusion.2"]
    assert (gelu.phase, gelu.unit) == ("bwd", "bert_layer_0")
    assert gelu.op_types == ("gelu",) and not gelu.has_dot
    assert found["copy.1"].phase == "none" and found["copy.1"].unit is None
    assert (found["rows"].phase, found["rows"].unit) == ("opt", "rows/merge")
    assert found["rows"].op_types == ("adagrad_row_packed",)
    assert found["dot.1"].text.startswith("%dot.1 = f32[8,8]")
