"""The account of set-up (observability/setup_account.py): every trace,
lowering, compile, cache read and first run of a step lands in a phase and
under the reason it was asked for, once, in the registry and in the tracer;
nothing of it reaches the lowered text."""
import importlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.observability import (get_flight_recorder, get_registry,
                                      get_tracer, setup_account)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FEED = {"x": np.ones((8, 8), "float32"), "y": np.ones((8, 1), "float32")}
STAGED = ("trace", "lower", "backend_compile", "cache_read")


def _build(width=16, remat=False):
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [8])
            y = fluid.layers.data("y", [1])
            with (fluid.remat_unit("blk") if remat else fluid.unit("blk")):
                h = fluid.layers.fc(x, width, act="relu")
                h = fluid.layers.fc(h, width, act="relu")
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(h, 1), y))
            fluid.optimizer.Adam(1e-3).minimize(loss)
    if remat:
        main.remat_policy = "full"
    return main, startup, loss


def _started(main, startup):
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return exe, scope


class Account:
    """What the account gained since the object was made."""

    def __init__(self):
        self._then = self._now()
        self._spans = len(get_tracer().spans())

    @staticmethod
    def _now():
        return {(s["name"], tuple(sorted(s["labels"].items()))): s["value"]
                for s in get_registry().series(deep=False)
                if s["name"].startswith("setup/")}

    def gained(self, name, **labels):
        total = 0.0
        for (have, items), value in self._now().items():
            if have == name and labels.items() <= dict(items).items():
                total += value - self._then.get((have, items), 0)
        return total

    def spans(self):
        """(span, parent span or None) of the spans begun since."""
        spans = get_tracer().spans()
        return [(s, spans[s["parent"]] if s["parent"] is not None else None)
                for s in spans[self._spans:]]


def _named(pairs, name, **args):
    return [(s, p) for s, p in pairs if s["name"] == name
            and args.items() <= s["args"].items()]


def test_a_first_call_gives_each_phase_once_under_call():
    main, startup, loss = _build(width=24)
    exe, scope = _started(main, startup)
    account = Account()
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    (step,) = [fn for key, fn in exe._cache.items() if key[0] == id(main)]
    pairs = account.spans()
    (root,) = [s for s, _ in _named(pairs, "executor/compile+run")]
    children = [s for s, p in pairs if p is root]
    names = [s["name"] for s in children]
    compiled = [n for n in names
                if n in ("setup/backend_compile", "setup/cache_read")]
    assert len(compiled) == 1
    assert names == ["setup/trace", "setup/lower", compiled[0],
                     "setup/relayout", "setup/first_run"]
    assert all(s["args"] == {"step": step.name, "reason": "call"}
               for s in children)
    # the registry holds what the spans show, and no second is counted twice
    seconds = {p: account.gained("setup/seconds", phase=p, reason="call")
               for p in setup_account.PHASES}
    for span in children:
        assert seconds[span["name"][len("setup/"):]] == pytest.approx(
            span["dur"] * 1e-6, rel=0.05, abs=2e-4)
    assert 0 < sum(seconds.values()) <= root["dur"] * 1e-6
    assert seconds["trace"] > 0 and seconds["lower"] > 0
    assert account.gained("setup/stagings", reason="call") == 1
    assert account.gained("setup/executables") == 1
    assert account.gained("setup/stagings") == 1      # under no other reason
    # the steady step after it has the children it had
    steady = [s for s, p in pairs if s["name"] == "executor/step"][-1]
    assert [s["name"] for s, p in pairs if p is steady] == [
        "executor/feed", "executor/state_in", "executor/run",
        "executor/telemetry", "executor/state_out", "executor/epilogue",
        "executor/fetch"]
    assert not [s for s, p in pairs if p is not None
                and p["name"] == "executor/run"]


def test_a_jit_inside_a_trace_is_held_by_the_outer_one():
    @jax.jit
    def inner(v):
        time.sleep(0.02)
        return v * 2

    def outer(v):
        return inner(v) + jnp.mean(v)      # jnp.mean is a jit of jax's own

    ones = jnp.ones((3,))
    account = Account()
    t0 = time.perf_counter()
    jax.jit(outer)(ones)
    wall = time.perf_counter() - t0
    traces = _named(account.spans(), "setup/trace")
    assert [s["args"]["step"] for s, _ in traces] == ["outer"]
    traced = account.gained("setup/seconds", phase="trace")
    assert 0.02 <= traced == pytest.approx(traces[0][0]["dur"] * 1e-6,
                                           rel=0.05)
    assert sum(account.gained("setup/seconds", phase=p)
               for p in setup_account.PHASES) <= wall


def test_a_jit_of_the_user_s_is_foreign_and_in_no_metric():
    readers = {name: importlib.import_module(
        f"benchmark.layer_metrics.{name}").read for name in (
            "setup_trace_s", "setup_lower_s", "setup_restage_s",
            "setup_first_run_s", "setup_cache_misses", "stagings_per_step",
            "setup_kernel_trace_s")}
    main, startup, loss = _build(width=20)
    exe, scope = _started(main, startup)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    before = {name: read({}) for name, read in readers.items()}
    assert all(v is not None for v in before.values())
    ones = jnp.ones((5, 5))
    account = Account()
    jax.jit(lambda v: jnp.tanh(v) @ v.T)(ones)
    for phase in ("trace", "lower"):
        assert account.gained("setup/seconds", phase=phase,
                              reason="foreign") > 0
    assert (account.gained("setup/seconds")
            == account.gained("setup/seconds", reason="foreign"))
    assert account.gained("setup/stagings") == 0
    assert account.gained("setup/cache_misses") == 0
    for s, parent in _named(account.spans(), "setup/trace"):
        assert parent["name"] == "executor/stage"
        assert parent["args"] == {"reason": "foreign"}
    assert {name: read({}) for name, read in readers.items()} == before


def test_a_step_lowered_by_someone_else_is_direct():
    main, startup, loss = _build(width=28)
    exe, scope = _started(main, startup)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    (step,) = [fn for key, fn in exe._cache.items() if key[0] == id(main)]
    state = {n: scope.find_var(n) for n in exe._state_names(main, scope)}
    account = Account()
    # what benchmark/program_access.py does on the plain-jit path
    step._plain.lower(state, {k: jnp.asarray(v) for k, v in FEED.items()},
                      scope.find_var("@RNG_STATE@")).compile()
    assert account.gained("setup/seconds", reason="direct") > 0
    assert (account.gained("setup/seconds")
            == account.gained("setup/seconds", reason="direct"))
    assert account.gained("setup/seconds", phase="lower",
                          reason="direct") > 0
    # jax keeps the jaxpr of a function it traced on these shapes: the
    # Program is not walked again, and that is no staging
    assert account.gained("setup/stagings") == 0
    assert account.gained("setup/executables") == 0
    staged = [(s, p) for s, p in account.spans()
              if s["name"].startswith("setup/")]
    assert staged and all(
        p["name"] == "executor/stage" and p["args"] == {"reason": "direct"}
        and s["args"]["step"] == step.name for s, p in staged)


def test_compiled_step_stages_nothing_on_the_aot_path_and_once_on_the_plain(
        monkeypatch):
    main, startup, loss = _build(width=32)
    exe, scope = _started(main, startup)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    account = Account()
    assert exe.compiled_step(main) is exe.compiled_step(main)
    assert account.gained("setup/seconds") == 0 and not account.spans()

    # the plain-jit path: a step whose AUTO-layout compile is refused
    def refuse(*a, **k):
        raise NotImplementedError("no AUTO layouts here")

    main, startup, loss = _build(width=36)
    exe, scope = _started(main, startup)
    events = len(get_flight_recorder().contents()["events"])
    fallbacks = Account()
    real_build = exe._build

    def build(*a, **k):
        step = real_build(*a, **k)
        monkeypatch.setattr(step._auto, "lower", refuse, raising=False)
        return step

    monkeypatch.setattr(exe, "_build", build)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    (step,) = [fn for key, fn in exe._cache.items() if key[0] == id(main)]
    assert step._compiled is None and step._auto is None
    assert fallbacks.gained("setup/auto_layout_fallbacks",
                            error="NotImplementedError") == 1
    assert fallbacks.gained("setup/auto_layout_fallbacks") == 1
    (note,) = get_flight_recorder().contents()["events"][events:]
    assert note["level"] == "warning" and note["step"] == step.name
    assert "NotImplementedError" in note["message"]
    assert "plain jit" in note["message"]
    # the plain jit's first call is the call's: one trace, one executable
    assert fallbacks.gained("setup/stagings", reason="call") == 1
    assert fallbacks.gained("setup/executables") == 1
    assert fallbacks.gained("setup/seconds", phase="first_run") > 0
    account = Account()
    compiled = exe.compiled_step(main)
    assert compiled is exe.compiled_step(main)
    roots = _named(account.spans(), "executor/stage", reason="executable")
    assert len(roots) == 1 and roots[0][1] is None
    assert (account.gained("setup/seconds")
            == account.gained("setup/seconds", reason="executable") > 0)
    # jax serves the trace from its own cache: at most one walk
    assert account.gained("setup/stagings", reason="executable") <= 1
    assert account.gained("setup/stagings") == account.gained(
        "setup/stagings", reason="executable")


@pytest.mark.parametrize("trace_cost", ["1", "0"])
def test_the_mesh_path_s_first_call_stages_for_the_cost_ledger(
        monkeypatch, trace_cost):
    monkeypatch.setenv("PDTPU_PERF_TRACE_COST", trace_cost)
    main, startup, loss = _build(width=40 + int(trace_cost))
    program = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=jax.devices()[:4])
    exe, scope = _started(main, startup)
    account = Account()
    exe.run(program, feed=FEED, fetch_list=[loss], scope=scope)
    pairs = account.spans()
    (root,) = [s for s, _ in _named(pairs, "compiled_program/compile+run")]
    under = [s["name"] for s, p in pairs if p is root]
    assert under[0] == "setup/first_run"      # the plain jit: all inside it
    (first_run,) = [s for s, p in pairs if s["name"] == "setup/first_run"]
    inside = [s for s, p in pairs if p is first_run]
    assert [s["name"] for s in inside][:2] == ["setup/trace", "setup/lower"]
    assert all(s["args"]["reason"] == "call" for s in inside)
    assert account.gained("setup/stagings", reason="call") == 1
    assert account.gained("setup/executables") == 1
    # first_run holds the call less what jax staged inside it
    staged = sum(account.gained("setup/seconds", phase=p, reason="call")
                 for p in STAGED)
    first = account.gained("setup/seconds", phase="first_run", reason="call")
    assert first + staged == pytest.approx(first_run["dur"] * 1e-6, rel=0.05)
    cost = _named(pairs, "executor/stage", reason="cost")
    if trace_cost == "1":
        assert len(cost) == 1
        assert cost[0][1]["name"] == "executor/telemetry"
        assert account.gained("setup/seconds", reason="cost") > 0
    else:
        assert not cost
        assert account.gained("setup/seconds", reason="cost") == 0


_RUNNER = """
import hashlib, json, sys
import numpy as np
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as fluid
from paddle_tpu.observability import get_registry
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data("x", [8])
    loss = fluid.layers.mean(fluid.layers.fc(x, 4, act="relu"))
    fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor()
exe.run(startup)
feed = {{"x": np.ones((2, 8), "float32")}}
exe.run(main, feed=feed, fetch_list=[loss])
(step,) = [fn for key, fn in exe._cache.items() if key[0] == id(main)]
scope = fluid.global_scope()
state = {{n: scope.find_var(n) for n in exe._state_names(main, scope)}}
text = step._plain.lower(state, {{"x": jnp.asarray(feed["x"])}},
                         scope.find_var("@RNG_STATE@")).as_text()
snap = {{k: v for k, v in get_registry().snapshot().items()
        if k.startswith("setup/") and "foreign" not in k
        and "direct" not in k}}
print(json.dumps({{"snap": snap, "step": step.name,
                  "text": hashlib.sha256(text.encode()).hexdigest()}}))
"""


def test_a_second_process_reads_the_cache_and_lowers_to_the_same_text(
        tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _RUNNER.format(repo=REPO)], env=env,
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = (r["snap"] for r in runs)
    compile_s = 'setup/seconds{phase="backend_compile",reason="call"}'
    read_s = 'setup/seconds{phase="cache_read",reason="call"}'
    assert cold[compile_s] > 0 and read_s not in cold
    assert cold["setup/cache_misses"] >= 2 and cold["setup/cache_hits"] == 0
    assert warm[read_s] > 0 and compile_s not in warm
    assert warm["setup/cache_misses"] == 0
    assert warm["setup/cache_hits"] == cold["setup/cache_misses"]
    assert warm['setup/stagings{reason="call"}'] == 2 == warm[
        "setup/executables"]
    assert warm["setup/import_seconds"] > 0
    # nothing of the account in the step's name or its text
    assert runs[0]["step"] == runs[1]["step"]
    assert runs[0]["text"] == runs[1]["text"]


class _Clock:
    """`time` as `setup_account` reads it, moved by the test alone: a loaded
    machine stretches a real sleep past any bound."""

    now = 100.0

    def perf_counter(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


def test_the_walk_s_clock_is_exclusive(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(setup_account, "time", clock)
    account = Account()
    with setup_account.walk("test_outer_op"):
        clock.sleep(0.02)
        with setup_account.walk("test_inner_op"):
            clock.sleep(0.03)
    assert account.gained("setup/trace_op_calls", op="test_inner_op") == 1
    assert account.gained("setup/trace_op_calls", op="test_outer_op") == 1
    inner_s = account.gained("setup/trace_op_seconds", op="test_inner_op")
    outer_s = account.gained("setup/trace_op_seconds", op="test_outer_op")
    assert inner_s == pytest.approx(0.03) and outer_s == pytest.approx(0.02)


def test_a_remat_unit_s_ops_and_the_autodiff_walk_sum_to_the_trace():
    main, startup, loss = _build(width=44, remat=True)
    exe, scope = _started(main, startup)
    account = Account()
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    by_op = {}
    for (name, items), _ in account._now().items():
        if name == "setup/trace_op_seconds":
            op = dict(items)["op"]
            by_op[op] = (account.gained(name, op=op),
                         account.gained("setup/trace_op_calls", op=op))
    by_op = {op: v for op, v in by_op.items() if v[1]}
    # the unit's ops are walked inside the group, once each
    assert by_op["remat_group"][1] == 1 and by_op["autodiff"][1] == 1
    assert by_op["mul"][1] == 3 and by_op["relu"][1] == 2
    assert by_op["adam"][1] == 6
    walked = sum(seconds for seconds, _ in by_op.values())
    traced = account.gained("setup/seconds", phase="trace", reason="call")
    # exclusive times add up to the walk, and the walk is most of the trace
    assert 0.5 * traced < walked <= traced
    assert by_op["autodiff"][0] < walked - by_op["autodiff"][0]


def test_a_kernel_is_traced_once_a_call_site():
    fa = importlib.import_module("paddle_tpu.ops.pallas_kernels.flash_attention")
    q = jnp.ones((1, 2, 128, 64))

    def loss(q):
        once = fa.flash_attention(q, q, q, causal=True)
        return jnp.sum(fa.flash_attention(once, q, q, causal=True) ** 2)

    account = Account()
    fa.FORCE_PALLAS_INTERPRET = True
    try:
        jax.jit(jax.grad(loss))(q)
    finally:
        fa.FORCE_PALLAS_INTERPRET = False
    for kernel in ("flash_fwd_onepass", "flash_bwd_onepass"):
        assert account.gained("setup/kernel_traces", kernel=kernel) == 2
        assert account.gained("setup/kernel_traces", kernel=kernel,
                              reason="foreign") == 2
        assert account.gained("setup/kernel_trace_seconds",
                              kernel=kernel) > 0
    assert (account.gained("setup/kernel_trace_seconds")
            < account.gained("setup/seconds", phase="trace"))


def test_the_experts_kernels_are_traced_once_a_shape_under_their_labels():
    """Two expert layers of one shape, forward and backward: the grouped
    product's kernels (ops/pallas_kernels/grouped_ffn.py) are bound inside a
    jitted function a shape, so each body is traced once, not once a layer,
    and the account counts it under the kernel's own label."""
    from paddle_tpu.parallel import moe
    kernels = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.grouped_ffn")
    gate, w1, _, w2, _, w3 = moe.init_moe_params(
        jax.random.PRNGKey(0), 24, 40, 4, gated=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 24))

    def loss(x, w1, w2, w3):
        for _ in range(2):
            x = x + moe.moe_ffn(x, gate, w1, None, w2, None, k=2,
                                act=jax.nn.silu, w3=w3).y
        return jnp.sum(x ** 2)

    for staged in (kernels._pack_rows, kernels._walk_forward,
                   kernels._walk_backward):
        staged.clear_cache()
    account = Account()
    kernels.FORCE_PALLAS_INTERPRET = True
    try:
        jax.jit(jax.grad(loss, (0, 1, 2, 3)))(x, w1, w2, w3)
    finally:
        kernels.FORCE_PALLAS_INTERPRET = False
    # the rows laid out forward and, with the cotangent beside them, backward
    for kernel, binds in (("grouped_ffn_fwd", 1), ("grouped_ffn_bwd", 1),
                          ("grouped_ffn_rows", 2)):
        assert account.gained("setup/kernel_traces", kernel=kernel) == binds
        assert account.gained("setup/kernel_trace_seconds",
                              kernel=kernel) > 0


_NO_PALLAS = """
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import paddle_tpu as fluid
from paddle_tpu.models import deepfm
main, startup, _, loss, _ = deepfm.build_train_program(
    vocab_size=5000, is_sparse=True, fused_table=True, lr=0.05,
    embedding_optimizer="adagrad", packed_rows={{"rows_per_step": 8 * 26}})
exe = fluid.Executor()
exe.run(startup)
rng = np.random.RandomState(0)
feed = {{"sparse_ids": rng.randint(0, 5000, (8, 26)).astype("int64"),
        "dense": rng.rand(8, 13).astype("float32"),
        "label": rng.randint(0, 2, (8, 1)).astype("float32")}}
(value,) = exe.run(main, feed=feed, fetch_list=[loss])
assert np.isfinite(np.asarray(value)).all()
print("PALLAS", sorted(m for m in sys.modules if m.startswith(
    ("jax.experimental.pallas", "paddle_tpu.ops.pallas_kernels"))))
"""


def test_a_program_without_a_kernel_loads_no_pallas():
    """`import paddle_tpu` and a step of DeepFM, which has no Pallas kernel,
    in a fresh interpreter: no Pallas module is loaded (1.3 s of `setup_s`,
    which refused PR 42 in the two `deepfm_criteo` cells). A kernel's module
    is imported where an op that takes it is lowered."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _NO_PALLAS.format(repo=REPO)], env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "PALLAS []"


def test_the_account_of_the_finite_probe_is_the_executor_s_own(monkeypatch):
    monkeypatch.setattr(executor_mod, "_FINITE_PROBE", None)
    ones = jnp.ones((7, 3))
    account = Account()
    executor_mod._check_finite([("v", ones)])
    assert account.gained("setup/seconds", reason="probe") > 0
    assert account.gained("setup/seconds", reason="foreign") == 0
    with pytest.raises(FloatingPointError, match="'w'"):
        executor_mod._check_finite([("w", jnp.full((7, 3), jnp.nan))])


def test_the_import_is_on_the_account():
    (imported,) = [s for s in get_tracer().export_chrome_trace()[
        "traceEvents"] if s.get("name") == "setup/import"
        and s["ph"] == "B"] or [None]
    total = get_registry().snapshot()["setup/import_seconds"]
    assert total > 0
    if imported is not None:      # the ring may have let it go
        assert imported["ts"] <= 0 or imported["ts"] < total * 1e6


def test_an_exported_trace_is_in_order_of_time():
    jax.jit(lambda v: v + 1)(jnp.ones((2,)))
    events = [e for e in get_tracer().export_chrome_trace()["traceEvents"]
              if e["ph"] in ("B", "E")]
    stamps = [e["ts"] for e in events]
    assert stamps == sorted(stamps)
