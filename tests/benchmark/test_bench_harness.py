"""The harness driven end to end on the CPU at tiny sizes: cells, mixes and a
layer metric added as files only are found; the plain references agree with
the system (float32, so tightly); a broken timed path comes out not correct;
`run.py` itself refuses to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import bench_fixtures as bf
from benchmark import check, harness


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bf.make_tree(tmp_path_factory.mktemp("bench_tree"))


def _run(tree, cell_name, build=None, trace=False, chips=1, seed=2**31 + 11):
    cell = harness.load_cell(cell_name, tree)
    lines = []
    result = harness.run_cell(
        cell, seed, 0.3, trace, time.perf_counter(), build=build,
        device=dict(bf.FAKE_DEVICE, count=chips),
        say=lambda *a, **k: lines.append(" ".join(map(str, a))))
    return cell, result, lines


def test_files_added_beside_the_real_ones_are_found(tree):
    cell = harness.load_cell("tiny_ernie.tiny_seq", tree)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["name"] == "tiny_seq"
    assert "readings_count" in cell.readers          # the added layer metric
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "step_ms", "setup_s"}
    assert "step_ms_p90" in cell.readers             # per layer: no bound
    real = harness.load_cell("ernie_base.dp4_seq512", tree)
    assert real.chips == 4 and "collective_ms" in real.readers
    assert "collective_ms" not in cell.readers
    with pytest.raises(harness.BenchmarkError, match="no workload"):
        harness.load_cell("ernie_base.nope", tree)


@pytest.mark.parametrize("cell_name,rate", [
    ("tiny_ernie.tiny_seq", "tokens_per_s"),
    ("tiny_deepfm.tiny_fields", "examples_per_s")])
def test_system_agrees_with_its_plain_reference(tree, cell_name, rate):
    cell, result, lines = _run(tree, cell_name)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {rate, "step_ms", "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert result["device"]["memory_peak_bytes"] > 0
    assert any("loss_gap" in line and "limit" in line for line in lines)
    # the last line of a run is this object, and it is JSON
    assert json.loads(json.dumps(result)) == result


def test_same_seed_same_inputs(tree):
    cell = harness.load_cell("tiny_deepfm.tiny_fields", tree)
    a = cell.generator.make_ring(cell.config, cell.traffic, 2**31 + 5)
    b = cell.generator.make_ring(cell.config, cell.traffic, 2**31 + 5)
    c = cell.generator.make_ring(cell.config, cell.traffic, 2**31 + 6)
    assert all(np.array_equal(x["sparse_ids"], y["sparse_ids"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["sparse_ids"], c[0]["sparse_ids"])
    assert a[0]["sparse_ids"].shape == c[0]["sparse_ids"].shape == (64, 26)
    assert a[0]["sparse_ids"].max() < cell.config["table_rows"]


def test_the_uniform_mix_draws_nearly_every_lookup_from_a_distinct_row():
    """`traffic/uniform.json` is data only: the same generator, the mix's own
    equal cardinalities and no skew. `fields` repeats three lookups in four."""
    cell = harness.load_cell("deepfm_criteo.fields", bf.REPO)
    with open(os.path.join(bf.REPO, "benchmark", "traffic",
                           "uniform.json")) as f:
        uniform = json.load(f)
    distinct = {}
    for mix in (cell.traffic, uniform):
        (batch,) = cell.generator.make_ring(cell.config, dict(mix, ring=1),
                                            2**31 + 21)
        assert batch["sparse_ids"].shape == (4096, 26)
        assert 0 <= batch["sparse_ids"].min()
        assert batch["sparse_ids"].max() < cell.config["table_rows"]
        distinct[mix["name"]] = len(np.unique(batch["sparse_ids"]))
    assert distinct["uniform"] > 0.99 * 4096 * 26
    assert 0.2 * 4096 * 26 < distinct["fields"] < 0.3 * 4096 * 26


def test_four_virtual_devices_run_the_data_parallel_construction(
        tmp_path_factory):
    tree4 = bf.make_tree(tmp_path_factory.mktemp("bench_tree4"), chips=4)
    cell, result, lines = _run(tree4, "tiny_ernie.tiny_seq", chips=4)
    assert cell.traffic["layout"] == "data_parallel"
    assert result["correct"] is True, lines
    assert result["device"]["count"] == 4


class _HalfTheBatchLeftOut:
    """The timed path with a part of the batch left out: the second half of
    every batch's rows carries no label."""

    def __init__(self, system):
        self._s = system

    def __getattr__(self, name):
        return getattr(self._s, name)

    def step(self, batch):
        labels = batch["mlm_labels"].copy()
        labels[labels.shape[0] // 2:] = -100
        return self._s.step(dict(batch, mlm_labels=labels))


class _StateUnchanged:
    """The timed path with a step that returns its state unchanged."""

    def __init__(self, system):
        self._s = system

    def __getattr__(self, name):
        return getattr(self._s, name)

    def step(self, batch):
        scope = self._s.scope
        kept = {n: jnp.copy(scope.find_var(n)) for n in scope.var_names()
                if not n.startswith("@")}
        loss = self._s.step(batch)
        for n, v in kept.items():
            scope.set_var(n, v)
        return loss


@pytest.mark.parametrize("broken", [_HalfTheBatchLeftOut, _StateUnchanged])
def test_a_broken_timed_path_is_not_correct(tree, broken):
    cell = harness.load_cell("tiny_ernie.tiny_seq", tree)
    _, result, lines = _run(
        tree, "tiny_ernie.tiny_seq",
        build=lambda *a: broken(cell.adapter.build(*a)))
    assert result["correct"] is False
    assert any("FAILED" in line for line in lines)
    assert result["attempted"] > 0          # the rest of the run was driven


def test_a_non_finite_loss_counts_as_failed(tree):
    cell = harness.load_cell("tiny_deepfm.tiny_fields", tree)

    class Nan:
        def __init__(self, system):
            self._s, self.n = system, 0

        def __getattr__(self, name):
            return getattr(self._s, name)

        def step(self, batch):
            self.n += 1
            loss = self._s.step(batch)
            return loss * np.nan if self.n > 12 else loss

    _, result, _ = _run(tree, "tiny_deepfm.tiny_fields",
                        build=lambda *a: Nan(cell.adapter.build(*a)))
    assert result["failed"] > 0 and result["correct"] is False


def test_run_py_on_the_cpu_exits_nonzero_and_names_the_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    proc = subprocess.run(
        [sys.executable, os.path.join(bf.REPO, "benchmark", "run.py"),
         "--workload", "ernie_base.seq512", "--seed", "3000000000",
         "--seconds", "1", "--trace", "0"],
        cwd=bf.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_py_prints_the_result_object_as_its_last_line():
    """`run.py`'s own glue past the look for a chip, with a TPU and the loop
    stood in for: `setup_s` is counted from the backend's being up, the time
    to get there is handed on, and the last line of stdout is the result
    object."""
    script = (
        "import runpy, sys, time\n"
        "import jax\n"
        "class Chip:\n"
        "    platform = 'tpu'; device_kind = 'TPU v5 lite'\n"
        "jax.devices = lambda *a: [Chip()]\n"
        "from benchmark import harness\n"
        "def run_cell(cell, seed, seconds, trace, t_start, backend_s):\n"
        "    assert 0 <= time.perf_counter() - t_start < 5\n"
        "    assert 0 < backend_s < 300\n"
        "    return {'correct': True, 'cell': cell.name, 'seed': seed,\n"
        "            'seconds': seconds, 'trace': trace}\n"
        "harness.run_cell = run_cell\n"
        "sys.argv = ['benchmark/run.py', '--workload', 'deepfm_criteo.fields',"
        " '--seed', '3000000001', '--seconds', '7', '--trace', '1']\n"
        "runpy.run_path('benchmark/run.py', run_name='__main__')\n")
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd=bf.REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=bf.REPO),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "correct": True, "cell": "deepfm_criteo.fields", "seed": 3000000001,
        "seconds": 7.0, "trace": True}
    assert "not counted in setup_s" in lines[-2]


def test_run_py_without_the_program_exits_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: no program to import, so no result."""
    import shutil
    root = tmp_path / "only_bench"
    shutil.copytree(os.path.join(bf.REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bf.REPO, "BENCHMARK.json"), root)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "deepfm_criteo.fields", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("config,mix,as_run", [
    # the configuration's own dropout, and a vocabulary large enough that
    # int8 rounds the output matrix's small gradient entries away, as it does
    # at 30522: Adam then leaves those weights where they were
    ("tiny_ernie", "tiny_seq", {"vocab_size": 1024, "hidden_dropout_prob": 0.1,
                                "attention_probs_dropout_prob": 0.1}),
    ("tiny_deepfm", "tiny_fields", {})])
def test_the_lower_precision_control_fails_the_cell_s_limits(tree, config, mix,
                                                             as_run):
    """The control (the reference in the nearest precision below the
    configuration's, under masks of its own) must come out as not correct
    under the limits the REAL configuration's file carries, while the float32
    reference against itself passes them trivially."""
    cell = harness.load_cell(f"{config}.{mix}", tree)
    cfg = dict(cell.config, **as_run)
    real = config.replace("tiny_ernie", "ernie_base").replace(
        "tiny_deepfm", "deepfm_criteo")
    with open(os.path.join(bf.REPO, "benchmark", "configs",
                           f"{real}.json")) as f:
        limits = json.load(f)["limits"]
    for seed in (2**31 + 1, 2**31 + 2, 2**31 + 3):
        head = cell.generator.make_ring(cfg, cell.traffic, seed)[:3]
        weights = cell.reference.make_weights(cfg, seed, head)
        sound = cell.reference.follow(cfg, weights, head, seed=seed)
        lower = cell.reference.follow(cfg, weights, head, control=True,
                                      seed=seed)
        assert check.compare(sound, sound, limits)["ok"]
        verdict = check.compare(lower, sound, limits)
        assert not verdict["ok"], check.format_numbers(verdict["numbers"])


def test_the_reference_s_dropout_is_seeded_and_its_own(tree):
    """Masks come from the seed (the same seed, the same numbers), differ
    between seeds and between the reference and its control's stream, and
    leave the loss where it was to within what a mask can move."""
    cell = harness.load_cell("tiny_ernie.tiny_seq", tree)
    on = dict(cell.config, hidden_dropout_prob=0.1,
              attention_probs_dropout_prob=0.1)
    seed = 2**31 + 9
    head = cell.generator.make_ring(on, cell.traffic, seed)[:3]
    weights = cell.reference.make_weights(on, seed, head)
    a = cell.reference.follow(on, weights, head, seed=seed)
    again = cell.reference.follow(on, weights, head, seed=seed)
    other = cell.reference.follow(on, weights, head, seed=seed + 1)
    off = cell.reference.follow(cell.config, weights, head, seed=seed)
    assert a == again
    assert a["losses"] != other["losses"] and a["losses"] != off["losses"]
    assert a["losses"][0] == pytest.approx(off["losses"][0], abs=0.1)
    assert check.worst_leaf_gap(a["grad_norms"], other["grad_norms"])[0] < 0.5
