"""Async input pipeline: DeviceLoader prefetch, FetchHandle fetches,
in-flight train_from_dataset, PyReader double buffering, and the
device-side FLAGS_check_nan_inf path."""
import os
import threading
import time

import numpy as np
import pytest

import jax
import paddle_tpu as fluid
from paddle_tpu.dataio import DeviceLoader, FetchHandle


def _batches(n, batch=2, dim=4, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(batch, dim).astype("float32")} for _ in range(n)]


def _no_loader_threads():
    return [t for t in threading.enumerate() if t.name.startswith("pdtpu-")]


def _build_sgd(dim=4):
    x = fluid.layers.data("x", [dim])
    h = fluid.layers.fc(x, 8, act="relu")
    loss = fluid.layers.mean(fluid.layers.fc(h, 3))
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss


# ---------------------------------------------------------------------------
# DeviceLoader
# ---------------------------------------------------------------------------

class TestDeviceLoader:
    def test_prefetch_preserves_order(self):
        data = [{"x": np.full((2, 4), i, "float32")} for i in range(20)]

        def jittery():
            rng = np.random.RandomState(3)
            for b in data:
                time.sleep(float(rng.uniform(0, 0.002)))
                yield b

        got = [float(np.asarray(b["x"]).mean())
               for b in DeviceLoader(jittery, capacity=3)]
        assert got == [float(i) for i in range(20)]

    def test_yields_device_arrays(self):
        loader = DeviceLoader(lambda: iter(_batches(2)), capacity=2)
        for b in loader:
            assert isinstance(b["x"], jax.Array)

    def test_reader_exception_reraises_in_consumer(self):
        def bad():
            yield {"x": np.zeros((2, 4), "float32")}
            yield {"x": np.zeros((2, 4), "float32")}
            raise ValueError("reader blew up")

        loader = DeviceLoader(bad, capacity=2)
        seen = 0
        with pytest.raises(ValueError, match="reader blew up"):
            for _ in loader:
                seen += 1
        assert seen == 2
        assert not loader.running
        assert _no_loader_threads() == []

    def test_exhaustion_leaves_no_threads(self):
        list(DeviceLoader(lambda: iter(_batches(5)), capacity=2))
        assert _no_loader_threads() == []

    def test_midepoch_break_then_close(self):
        def slow():
            for b in _batches(100):
                time.sleep(0.001)
                yield b

        loader = DeviceLoader(slow, capacity=2)
        for i, _ in enumerate(loader):
            if i == 3:
                break
        loader.close()
        loader.close()  # idempotent
        assert not loader.running
        assert _no_loader_threads() == []

    def test_reiteration_is_a_fresh_epoch(self):
        loader = DeviceLoader(lambda: iter(_batches(4)), capacity=2)
        assert len(list(loader)) == 4
        assert len(list(loader)) == 4

    def test_close_from_other_thread_unblocks_consumer(self):
        def endless():
            i = 0
            while True:
                yield {"x": np.full((1,), i, "float32")}
                i += 1

        loader = DeviceLoader(endless, capacity=2)
        it = iter(loader)
        next(it)
        threading.Timer(0.05, loader.close).start()
        # consumer either sees end-of-epoch or keeps yielding until the
        # close lands; it must not hang
        for _ in it:
            pass
        assert not loader.running

    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            DeviceLoader(lambda: iter([]), capacity=0)

    def test_feed_validation_applies_in_worker(self):
        # program-aware conversion: the prefetch path must reject what the
        # sync path rejects (declared-shape mismatch), in the consumer
        fluid.layers.data("x", [4])
        prog = fluid.default_main_program()
        loader = DeviceLoader(
            lambda: iter([{"x": np.zeros((2, 5), "float32")}]),
            capacity=2, program=prog)
        with pytest.raises(ValueError, match="shape mismatch"):
            list(loader)

    def test_telemetry_populated(self):
        from paddle_tpu.observability import get_registry
        list(DeviceLoader(lambda: iter(_batches(3)), capacity=2))
        snap = get_registry().snapshot()
        assert snap["dataio/batches"] >= 3
        assert snap["dataio/h2d_ms"]["count"] >= 3


# ---------------------------------------------------------------------------
# FetchHandle / Executor.run(return_handle=True)
# ---------------------------------------------------------------------------

class TestFetchHandle:
    def test_bitwise_identical_to_sync_run(self):
        loss = _build_sgd()
        exe = fluid.Executor(fluid.TPUPlace())
        feeds = _batches(4)

        with fluid.scope_guard(fluid.Scope()):
            exe.run(fluid.default_startup_program())
            sync = [exe.run(feed=f, fetch_list=[loss])[0] for f in feeds]
        with fluid.scope_guard(fluid.Scope()):
            exe.run(fluid.default_startup_program())
            handles = [exe.run(feed=f, fetch_list=[loss],
                               return_handle=True) for f in feeds]
            async_ = [h.numpy()[0] for h in handles]
        for a, b in zip(sync, async_):
            assert a.dtype == b.dtype
            assert np.array_equal(a, b)

    def test_handle_protocol(self):
        loss = _build_sgd()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        h = exe.run(feed=_batches(1)[0], fetch_list=[loss],
                    return_handle=True)
        assert isinstance(h, FetchHandle)
        assert len(h) == 1
        assert h.names == [loss.name]
        assert isinstance(h.jax()[0], jax.Array)
        h.block_until_ready()
        assert h.is_ready()
        assert np.array_equal(h[0], h.numpy()[0])
        assert "materialized" in repr(h)

    def test_fetchless_handle_carries_probe(self):
        _build_sgd()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        h = exe.run(feed=_batches(1)[0], fetch_list=[],
                    return_handle=True)
        assert len(h) == 0 and h.numpy() == []
        h.block_until_ready()  # must not raise: blocks on the state probe


# ---------------------------------------------------------------------------
# train_from_dataset in-flight pipeline
# ---------------------------------------------------------------------------

class _FakeDataset:
    """Anything with batches()/set_thread() drives train_from_dataset."""

    def __init__(self, data):
        self.data = data

    def set_thread(self, n):
        pass

    def batches(self):
        for b in self.data:
            # extra key not declared by the program must be filtered out
            yield dict(b, junk=np.zeros(3))


class TestTrainFromDataset:
    def test_inflight_2_matches_inflight_1(self):
        loss = _build_sgd()
        exe = fluid.Executor(fluid.TPUPlace())
        data = _batches(7, seed=11)

        def arm(inflight):
            old = fluid.get_flags("max_inflight_steps")
            fluid.set_flags({"max_inflight_steps": inflight})
            try:
                with fluid.scope_guard(fluid.Scope()):
                    exe.run(fluid.default_startup_program())
                    return exe.train_from_dataset(
                        dataset=_FakeDataset(data), fetch_list=[loss])
            finally:
                fluid.set_flags(old)

        a, b = arm(1), arm(2)
        assert np.array_equal(a[0], b[0])
        assert _no_loader_threads() == []

    def test_no_fetch_list(self):
        _build_sgd()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        out = exe.train_from_dataset(dataset=_FakeDataset(_batches(3)))
        assert out == []

    def test_empty_dataset_returns_none(self):
        _build_sgd()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        assert exe.train_from_dataset(dataset=_FakeDataset([])) is None

    def test_executor_close_sweeps_loaders(self):
        exe = fluid.Executor(fluid.TPUPlace())
        loader = DeviceLoader(lambda: iter(_batches(50)), capacity=2)
        loader.start()
        exe._loaders.add(loader)
        assert loader.running
        exe.close()
        assert not loader.running


# ---------------------------------------------------------------------------
# PyReader double buffering
# ---------------------------------------------------------------------------

class TestPyReader:
    def _gen(self, n=5):
        def gen():
            for i in range(n):
                yield [(np.full(4, i, "float32"),) for _ in range(2)]
        return gen

    def test_double_buffer_yields_device_batches_in_order(self):
        x = fluid.layers.data("x", [4])
        r = fluid.PyReader(feed_list=[x], capacity=8, use_double_buffer=True)
        r.decorate_sample_list_generator(self._gen())
        vals = []
        for feed in r():
            assert isinstance(feed["x"], jax.Array)
            vals.append(float(np.asarray(feed["x"]).mean()))
        assert vals == [0.0, 1.0, 2.0, 3.0, 4.0]
        assert _no_loader_threads() == []

    def test_double_buffer_matches_plain(self):
        x = fluid.layers.data("x", [4])
        loss = fluid.layers.mean(fluid.layers.fc(x, 3))
        exe = fluid.Executor(fluid.TPUPlace())

        def arm(db):
            r = fluid.PyReader(feed_list=[x], capacity=8,
                               use_double_buffer=db)
            r.decorate_sample_list_generator(self._gen())
            with fluid.scope_guard(fluid.Scope()):
                exe.run(fluid.default_startup_program())
                return [exe.run(feed=f, fetch_list=[loss])[0] for f in r()]

        for a, b in zip(arm(False), arm(True)):
            assert np.array_equal(a, b)

    def test_reset_tears_down_prefetch_thread(self):
        x = fluid.layers.data("x", [4])
        r = fluid.PyReader(feed_list=[x], capacity=8, use_double_buffer=True)
        r.decorate_sample_list_generator(self._gen(100))
        it = r()
        next(it)
        assert r._loader is not None and r._loader.running
        r.reset()
        r.reset()  # idempotent
        assert r._loader is None
        assert _no_loader_threads() == []

    def test_undecorated_reader_raises(self):
        r = fluid.PyReader(feed_list=[], capacity=4)
        with pytest.raises(RuntimeError, match="decorate"):
            r()

    def test_layers_py_reader_constructs(self):
        # regression: shapes/dtypes kwargs used to raise TypeError
        r = fluid.layers.py_reader(4, [[4]], ["float32"])
        assert isinstance(r, fluid.PyReader)
        r2 = fluid.layers.create_py_reader_by_data(
            4, [fluid.layers.data("x", [4])])
        assert r2._feed_names == ["x"]

    def test_layers_double_buffer_prefetches(self):
        def reader():
            for b in _batches(3):
                yield b

        db = fluid.layers.double_buffer(reader)
        out = list(db())
        assert len(out) == 3 and isinstance(out[0]["x"], jax.Array)


# ---------------------------------------------------------------------------
# FLAGS_check_nan_inf device-side probe
# ---------------------------------------------------------------------------

class TestCheckNanInf:
    def test_nan_feed_raises_with_name(self):
        x = fluid.layers.data("x", [4])
        loss = fluid.layers.mean(fluid.layers.fc(x, 3))
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        fluid.set_flags({"check_nan_inf": True})
        try:
            with pytest.raises(FloatingPointError, match="NaN/Inf"):
                exe.run(feed={"x": np.full((2, 4), np.nan, "float32")},
                        fetch_list=[loss])
        finally:
            fluid.set_flags({"check_nan_inf": False})

    def test_finite_run_passes(self):
        loss = _build_sgd()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        fluid.set_flags({"check_nan_inf": True})
        try:
            out = exe.run(feed=_batches(1)[0], fetch_list=[loss])
            assert np.isfinite(out[0]).all()
        finally:
            fluid.set_flags({"check_nan_inf": False})


# ---------------------------------------------------------------------------
# flags / persistent compilation cache
# ---------------------------------------------------------------------------

class TestFlagsAndCompileCache:
    def test_env_aliases_bootstrap(self, monkeypatch):
        from paddle_tpu import flags as flags_mod
        old = dict(flags_mod._FLAGS)
        monkeypatch.setenv("PDTPU_MAX_INFLIGHT_STEPS", "4")
        try:
            flags_mod._bootstrap_from_env()
            assert flags_mod.flag("max_inflight_steps") == 4
        finally:
            flags_mod._FLAGS.update(old)

    @staticmethod
    def _repo_cache_dir():
        import paddle_tpu
        return os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(paddle_tpu.__file__))), ".jax_cache")

    def test_cache_dir_in_effect_after_import(self):
        """This process imported paddle_tpu long ago: the cache directory
        is the one the environment names, else <repo>/.jax_cache — and an
        Executor() does not move it."""
        want = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or self._repo_cache_dir())
        assert jax.config.jax_compilation_cache_dir == want
        fluid.Executor()
        assert jax.config.jax_compilation_cache_dir == want

    def test_default_dir_is_set_when_env_is_not(self, monkeypatch):
        from paddle_tpu.core import executor as exe_mod
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = {}
        monkeypatch.setattr(jax.config, "update", calls.__setitem__)
        assert exe_mod._enable_compile_cache() == self._repo_cache_dir()
        assert calls["jax_compilation_cache_dir"] == self._repo_cache_dir()
        # small and fast compiles are cached too
        assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_env_dir_is_left_to_jax(self, tmp_path, monkeypatch):
        """Where JAX_COMPILATION_CACHE_DIR is set the code sets no
        directory, and the entry count at start lands in the registry."""
        from paddle_tpu.core import executor as exe_mod
        (tmp_path / "jit_f-0123-cache").write_bytes(b"x")
        (tmp_path / "jit_f-0123-atime").write_bytes(b"x")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = {}
        monkeypatch.setattr(jax.config, "update", calls.__setitem__)
        assert exe_mod._enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in calls
        from paddle_tpu.observability import get_registry
        snap = get_registry().snapshot()
        assert snap["executor/compile_cache_entries_at_start"] == 1

    def test_env_dir_wins_in_a_fresh_interpreter(self, tmp_path):
        """End to end: jax reads the variable itself, entries land there
        and <repo>/.jax_cache is not what the config names."""
        import subprocess
        import sys
        code = (
            "import jax, paddle_tpu as fluid\n"
            "fluid.Executor()\n"
            "import jax.numpy as jnp\n"
            "jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=300, cwd=os.path.dirname(
                self._repo_cache_dir()))
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
        assert any(f.endswith("-cache") for f in os.listdir(tmp_path))


# ---------------------------------------------------------------------------
# DeviceLoader deterministic resume (state / restore_state)
# ---------------------------------------------------------------------------

class TestDeviceLoaderResume:
    """The (epoch, cursor) contract run_elastic checkpoints as @dataio@*:
    a restored loader replays exactly the batches the consumer never saw."""

    @staticmethod
    def _epoch_reader(epoch):
        # batch b of epoch e is the constant e*10 + b: any cursor slip is
        # instantly visible in the delivered values
        for b in range(4):
            yield {"x": np.full((2, 2), epoch * 10 + b, "float32")}

    @staticmethod
    def _vals(batches):
        return [int(np.asarray(b["x"])[0, 0]) for b in batches]

    def test_mid_epoch_state_resume_replays_undelivered_batches(self):
        l1 = DeviceLoader(self._epoch_reader, capacity=2)
        it = iter(l1)
        got = [next(it) for _ in range(3)]
        assert self._vals(got) == [0, 1, 2]
        st = l1.state()
        l1.close()
        assert st == {"version": 1, "epoch": 0, "cursor": 3}

        # prefetched-but-undelivered batches were NOT counted: a fresh
        # loader restored from st continues at batch 3, not at the
        # worker's read-ahead position
        l2 = DeviceLoader(self._epoch_reader, capacity=2)
        l2.restore_state(st)
        assert self._vals(list(l2)) == [3]          # rest of epoch 0
        assert self._vals(list(l2)) == [10, 11, 12, 13]  # then epoch 1

    def test_epoch_boundary_state(self):
        ld = DeviceLoader(self._epoch_reader, capacity=2)
        assert self._vals(list(ld)) == [0, 1, 2, 3]
        st = ld.state()
        assert st == {"version": 1, "epoch": 1, "cursor": 0}
        l2 = DeviceLoader(self._epoch_reader, capacity=2)
        l2.restore_state(st)
        assert self._vals(list(l2)) == [10, 11, 12, 13]

    def test_stateless_reader_still_resumes_by_skip(self):
        def reader():  # no epoch arg: plain fluid-style callable
            for b in range(5):
                yield {"x": np.full((1, 1), b, "float32")}

        l1 = DeviceLoader(reader, capacity=2)
        it = iter(l1)
        next(it), next(it)
        st = l1.state()
        l1.close()
        l2 = DeviceLoader(reader, capacity=2)
        l2.restore_state(st)
        assert self._vals(list(l2)) == [2, 3, 4]

    def test_restore_state_rejects_running_or_bad_state(self):
        ld = DeviceLoader(self._epoch_reader, capacity=2)
        it = iter(ld)
        next(it)
        with pytest.raises(RuntimeError, match="running"):
            ld.restore_state({"version": 1, "epoch": 0, "cursor": 1})
        ld.close()
        with pytest.raises(ValueError, match="version"):
            ld.restore_state({"version": 2, "epoch": 0, "cursor": 0})
        with pytest.raises(ValueError):
            ld.restore_state({"version": 1, "epoch": -1, "cursor": 0})

    def test_close_mid_epoch_does_not_advance_epoch(self):
        # close() wakes a blocked consumer with an _EndOfEpoch sentinel;
        # that teardown signal must not look like a real epoch end
        ld = DeviceLoader(self._epoch_reader, capacity=2)
        it = iter(ld)
        next(it)
        ld.close()
        assert ld.state() == {"version": 1, "epoch": 0, "cursor": 1}
