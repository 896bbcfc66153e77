"""Device-resident hot-row cache over the PS tier (ps.hot_cache).

The load-bearing claim (ISSUE 12): with ``hot_rows > 0`` the program's
cache param becomes a persistent LFU-managed slab — hit rows never
cross HBM<->host — and single-worker training stays BITWISE identical
to the uncached tier (and therefore to the single-table packed
baseline): every shard count, any prefetch/push depth, cache smaller
OR larger than the working set, and straight through a SIGKILLed
pserver. Plus: the shared slab bookkeeping (ps.slab), the plan/commit
concurrency rules (dirty-at-commit, in-flight slot pinning, pending
evictions in flush), the checkpoint flush hook, the slab's device
gather/scatter, and the ps_admin hot-cache block.
"""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability.registry import get_registry
from paddle_tpu.parallel.checkpoint import Checkpointer
from paddle_tpu.ps import (FreqSketch, HotRowCache, LruOrder,
                           PsEmbeddingTier, PsTableBinding, RangeSpec,
                           ShardedTable, SlotMap, SocketClient)

import test_ps_embedding as tpe
import test_ps_faults as tpf

V, CAP, LANES = tpe.V, tpe.CAP, tpe.LANES


@pytest.fixture(scope="module")
def ref():
    """(feeds, baseline losses, baseline final table) — computed once."""
    feeds = tpe._feeds()
    losses, final = tpe._packed_baseline(feeds)
    return feeds, losses, final


# ------------------------------------------------------------- slab core

def test_slotmap_dict_and_dense_modes_agree():
    for vocab in (None, 100):
        m = SlotMap(3, vocab=vocab)
        s0, s1 = m.assign(10), m.assign(20)
        assert (m.get(10), m.get(20), m.get(30)) == (s0, s1, None)
        assert m.get_many(np.array([10, 30, 20])).tolist() == [s0, -1, s1]
        assert 10 in m and 30 not in m
        assert len(m) == 2 and m.free_slots == 1
        assert m.uid_of(s0) == 10
        assert m.uids_at(np.array([s1]))[0] == 20
        assert m.pop(10) == s0 and m.get(10) is None
        # LIFO recycle: the next assign reuses the popped slot — the
        # invariant both caches' slab storage leans on
        assert m.assign(99) == s0
        uids, slots = m.residents()
        assert sorted(uids.tolist()) == [20, 99] and slots.size == 2
        m.clear()
        assert len(m) == 0 and m.get(99) is None and m.free_slots == 3
    full = SlotMap(1)
    full.assign(1)
    with pytest.raises(RuntimeError, match="full"):
        full.assign(2)


def test_lru_order_coldest_pops_first():
    lru = LruOrder()
    for u in (1, 2, 3):
        lru.touch(u)
    lru.touch(1)                 # 2 is now the coldest
    assert lru.pop_coldest() == 2
    lru.discard(3)
    assert lru.pop_coldest() == 1
    assert len(lru) == 0


def test_freq_sketch_overcounts_only_and_decays():
    sk = FreqSketch(width=1 << 10, depth=4, decay_every=10_000)
    sk.observe(np.full(50, 7, np.int64))
    sk.observe(np.array([3], np.int64))
    est = sk.estimate(np.array([7, 3, 999], np.int64))
    assert int(est[0]) >= 50     # min-over-rows can only over-count
    assert int(est[1]) >= 1
    assert int(est[2]) <= 1      # unseen id stays cold
    # halving decay: hitting decay_every halves every counter
    sk2 = FreqSketch(width=1 << 10, decay_every=64)
    sk2.observe(np.full(64, 5, np.int64))
    assert int(sk2.estimate(np.array([5], np.int64))[0]) == 32
    with pytest.raises(ValueError, match="power of two"):
        FreqSketch(width=100)


# --------------------------------------------------- HotRowCache planning

def _mk_cache(capacity=4, step_rows=8, min_freq=2, **kw):
    return HotRowCache(capacity, step_rows, lanes=LANES, vocab=V,
                       min_freq=min_freq, **kw)


def test_one_touch_ids_bypass_then_admit_then_hit():
    hc = _mk_cache()
    u = np.array([1, 2, 3], np.int64)
    p1 = hc.plan(u)
    # first touch: estimated frequency 1 < min_freq 2 — everything
    # stages through the bypass tail, nothing enters the resident region
    assert p1.n_hit == 0 and p1.n_admit == 0
    assert (p1.slots >= hc.capacity).all()
    assert p1.bypass_uids.tolist() == [1, 2, 3]
    hc.commit(p1)
    p2 = hc.plan(u)              # second touch: admitted
    assert p2.n_admit == 3 and p2.n_hit == 0
    assert (p2.slots < hc.capacity).all()
    assert p2.bypass_uids.size == 0
    hc.commit(p2)
    p3 = hc.plan(u)              # resident: pure hits, nothing pulled
    assert p3.n_hit == 3 and p3.miss_uids.size == 0
    hc.commit(p3)
    st = hc.stats()
    assert st["resident"] == 3 and st["hits"] == 3 and st["misses"] == 6
    assert st["admitted"] == 3 and st["bypass"] == 3


def test_occurrence_weighted_lookup_hit_rate():
    hc = _mk_cache(min_freq=1)
    u = np.array([1, 2], np.int64)
    hc.commit(hc.plan(u, np.array([5, 1], np.int64)))   # 6 cold lookups
    hc.commit(hc.plan(u, np.array([10, 2], np.int64)))  # 12 hit lookups
    st = hc.stats()
    assert st["hits"] == 2 and st["misses"] == 2
    assert st["hit_rate"] == 0.5                        # unique rows
    assert st["lookup_hits"] == 12 and st["lookup_misses"] == 6
    assert st["lookup_hit_rate"] == 12 / 18             # raw lookups


def test_step_rows_overflow_is_a_sizing_error():
    hc = _mk_cache(capacity=2, step_rows=4)
    with pytest.raises(ValueError, match="staging"):
        hc.plan(np.arange(5, dtype=np.int64))
    with pytest.raises(ValueError):
        HotRowCache(0, 4, lanes=LANES, vocab=V)


def test_sampled_lfu_evicts_cold_and_reuses_the_slot():
    hc = _mk_cache(capacity=2, step_rows=8, min_freq=1)
    p = hc.plan(np.array([10, 11], np.int64))
    hc.commit(p)                 # cache full with two one-touch ids
    assert hc.stats()["resident"] == 2
    for _ in range(4):           # heat uid 20 in the sketch
        hc._sketch.observe(np.array([20], np.int64))
    p2 = hc.plan(np.array([20], np.int64))
    assert p2.n_admit == 1 and p2.evict_uids.size == 1
    assert int(p2.evict_uids[0]) in (10, 11)
    # LIFO slot recycle: the admitted uid lands in the victim's slot
    assert int(p2.slots[0]) == int(p2.evict_slots[0])
    hc.commit(p2)


def test_eviction_tie_keeps_incumbent():
    hc = _mk_cache(capacity=1, step_rows=8, min_freq=1)
    hc.commit(hc.plan(np.array([5], np.int64)))
    p2 = hc.plan(np.array([6], np.int64))   # same estimate: no churn
    assert p2.n_admit == 0 and p2.evict_uids.size == 0
    assert p2.bypass_uids.tolist() == [6]
    hc.commit(p2)


def test_inflight_slots_are_never_victims():
    hc = _mk_cache(capacity=2, step_rows=8, min_freq=1)
    pinned = hc.plan(np.array([1, 2], np.int64))  # NOT yet dispatched
    hc._sketch.observe(np.full(8, 30, np.int64))
    p = hc.plan(np.array([30], np.int64))
    # both resident slots belong to an undispatched plan — admission
    # must fall back to bypass rather than steal a referenced slot
    assert p.n_admit == 0 and p.bypass_uids.tolist() == [30]
    hc.commit(p)
    hc.commit(pinned)


def test_flush_rows_dirty_at_commit_plus_pending_evicts():
    hc = _mk_cache(capacity=2, step_rows=8, min_freq=1)
    p = hc.plan(np.array([3, 4], np.int64))
    # between plan and commit nothing is dirty: the update has not run,
    # so a checkpoint flush here must not claim slab bytes are newer
    u, _ = hc.flush_rows()
    assert u.size == 0
    hc.commit(p)
    u, s = hc.flush_rows()       # dirty set at COMMIT, uid-ascending
    assert u.tolist() == [3, 4] and s.size == 2
    u, _ = hc.flush_rows()       # flush cleared the dirty bits
    assert u.size == 0
    # a planned-but-undispatched eviction: the victim's bytes still sit
    # in its old slot, and flush must write them back under the OLD uid
    hc._sketch.observe(np.full(8, 9, np.int64))
    p2 = hc.plan(np.array([9], np.int64))
    assert p2.evict_uids.size == 1
    vu, vs = int(p2.evict_uids[0]), int(p2.evict_slots[0])
    u, s = hc.flush_rows()
    assert u.tolist() == [vu] and s.tolist() == [vs]
    hc.commit(p2)


# ------------------------------------------------ slab device ops

def test_hot_cache_device_ops_roundtrip():
    import jax.numpy as jnp
    hc = _mk_cache(capacity=4, step_rows=4)
    rng = np.random.RandomState(2)
    rows = jnp.asarray(rng.randint(0, 2 ** 16, (3, LANES))
                       .astype(np.uint16))
    hc.insert_rows(np.array([1, 3, 6], np.int32), rows)
    got = np.asarray(hc.take_rows(np.array([1, 3, 6], np.int32)))
    np.testing.assert_array_equal(got[:3], np.asarray(rows))
    # pad tail repeats the last row (the pusher slices [:n])
    np.testing.assert_array_equal(got[3], got[2])


# -------------------------------------------------- bitwise training matrix

def _hot_run(feeds, spec, pull_ahead, push_depth, hot_rows):
    """tpe._ps_run with the hot cache on: slab-sized cache param
    ([hot_rows + CAP] rows) and hot_rows handed to the tier."""
    main, startup, loss = tpe._build_program(hot_rows + CAP)
    table = ShardedTable.build_in_process("tb", spec,
                                          full_rows=tpe._init_packed())
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        tier = PsEmbeddingTier(main, [PsTableBinding("tb", table, ["ids"])],
                               pull_ahead=pull_ahead,
                               push_depth=push_depth, hot_rows=hot_rows)
        try:
            for prep in tier.steps(lambda: iter(feeds)):
                (lv,) = tier.run_step(exe, prep, fetch_list=[loss])
                losses.append(float(np.asarray(lv)))
            tier.flush()
            stats = tier.stats()["tb"]["hot_cache"]
            final = table.dump_full()
        finally:
            tier.close()
    return losses, final, stats


@pytest.mark.parametrize("pull_ahead,push_depth", [(0, 0), (2, 1)])
@pytest.mark.parametrize("hot_rows,min_freq", [(8, None), (64, 1)])
def test_hot_training_bitwise_exact(monkeypatch, ref, pull_ahead,
                                    push_depth, hot_rows, min_freq):
    """THE acceptance matrix: shard counts 1/2/4 + uneven ranges ×
    inline and overlapped pull/push × a cache smaller than the working
    set (churn: admissions, evictions, write-backs all fire) and one
    larger than it (everything resident after first touch) — losses AND
    final shard bytes bitwise-equal to the packed baseline."""
    if min_freq is not None:
        monkeypatch.setenv("PDTPU_PS_ADMIT_MIN_FREQ", str(min_freq))
    feeds, ref_losses, ref_final = ref
    for spec in tpe.SPECS:
        losses, final, st = _hot_run(feeds, spec, pull_ahead, push_depth,
                                     hot_rows)
        assert losses == ref_losses, \
            (spec.to_dict(), pull_ahead, push_depth, hot_rows)
        np.testing.assert_array_equal(final, ref_final)
        if hot_rows < V:
            # the churn cell must actually churn, or it proved nothing
            assert st["evictions"] > 0 and st["writeback_bytes"] > 0
        else:
            assert st["evictions"] == 0
            assert st["hit_rate"] is not None and st["hit_rate"] > 0.5


def test_checkpoint_save_flushes_dirty_slab_rows(tmp_path, ref):
    """Checkpointer.save must invoke the table's flush hook: rows whose
    newest bytes live only in the slab reach the shards BEFORE the
    journal mark + dump, so the checkpoint is coherent without an
    explicit tier.flush()."""
    feeds, ref_losses, ref_final = ref
    hot_rows = 8
    main, startup, loss = tpe._build_program(hot_rows + CAP)
    table = ShardedTable.build_in_process(
        "tb", RangeSpec.even(V, 2), full_rows=tpe._init_packed())
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    sc = fluid.Scope()
    with fluid.scope_guard(sc):
        exe.run(startup)
        tier = PsEmbeddingTier(main, [PsTableBinding("tb", table, ["ids"])],
                               pull_ahead=1, push_depth=1,
                               hot_rows=hot_rows)
        try:
            for prep in tier.steps(lambda: iter(feeds)):
                (lv,) = tier.run_step(exe, prep, fetch_list=[loss])
                losses.append(float(np.asarray(lv)))
            assert tier.stats()["tb"]["hot_cache"]["dirty"] > 0
            ck = Checkpointer(str(tmp_path))
            ck.save(1, program=main, scope=sc, blocking=True,
                    ps_tables={"tb": table})
        finally:
            tier.close()
    assert losses == ref_losses
    full, mark, step = ck.load_ps_table("tb")
    assert step == 1
    np.testing.assert_array_equal(full, ref_final)


def test_sigkill_pserver_recovery_bitwise_with_hot_cache(tmp_path,
                                                         monkeypatch, ref):
    """The PR-10 flagship chaos cell with the hot cache on: SIGKILL one
    socket pserver mid-run, recover from checkpoint + journal replay —
    cache write-backs ride the same journal, so the run still finishes
    bitwise-identical to the uninterrupted packed baseline."""
    tpf._fast_retry(monkeypatch)
    feeds, ref_losses, ref_final = ref
    hot_rows = 8
    spec = RangeSpec.even(V, 2)
    procs, eps = [], []
    for i in range(2):
        lo, hi = spec.bounds(i)
        p, ep = tpf._launch_pserver([f"tb:{lo}:{hi}"])
        procs.append(p)
        eps.append(ep)
    clients = [SocketClient(ep) for ep in eps]
    table = ShardedTable("tb", spec, clients)
    reg = get_registry()
    recov0 = reg.counter("ps/recoveries").value
    restarter = None
    try:
        table.load_full(tpe._init_packed())
        main, startup, loss = tpe._build_program(hot_rows + CAP)
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        sc = fluid.Scope()
        with fluid.scope_guard(sc):
            exe.run(startup)
            ck = Checkpointer(str(tmp_path / "ck"))
            ck.save(0, program=main, scope=sc, blocking=True,
                    ps_tables={"tb": table})
            tier = PsEmbeddingTier(
                main, [PsTableBinding("tb", table, ["ids"])],
                pull_ahead=1, push_depth=0, hot_rows=hot_rows)
            tier.attach_checkpointer(ck)
            try:
                step = 0
                for prep in tier.steps(lambda: iter(feeds)):
                    if step == 5:
                        procs[1].kill()   # SIGKILL: a real preemption
                        procs[1].wait()
                        lo1, hi1 = spec.bounds(1)
                        port1 = int(eps[1].rsplit(":", 1)[1])

                        def _restart():
                            time.sleep(0.3)
                            procs[1], _ = tpf._launch_pserver(
                                [f"tb:{lo1}:{hi1}"], port=port1)

                        restarter = threading.Thread(target=_restart,
                                                     daemon=True)
                        restarter.start()
                    (lv,) = tier.run_step(exe, prep, fetch_list=[loss])
                    losses.append(float(np.asarray(lv)))
                    step += 1
                tier.flush()
                final = table.dump_full()
            finally:
                tier.close()
        recoveries = reg.counter("ps/recoveries").value - recov0
    finally:
        if restarter is not None:
            restarter.join(timeout=10.0)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert losses == ref_losses
    np.testing.assert_array_equal(final, ref_final)
    assert recoveries >= 1


# ------------------------------------------------------------ ps_admin view

def test_ps_admin_cache_fields_local_registry(ref):
    from paddle_tpu.tools import ps_admin
    feeds, _, _ = ref
    before = ps_admin.cache_fields() or {"hits": 0, "writeback_bytes": 0}
    _, _, st = _hot_run(feeds, tpe.SPECS[1], 1, 0, 8)
    cache = ps_admin.cache_fields()
    assert cache is not None and cache["capacity"] >= 8
    # registry counters advanced by exactly this run's local mirrors
    assert cache["hits"] - before["hits"] == st["hits"]
    assert (cache["writeback_bytes"] - before["writeback_bytes"]
            == st["writeback_bytes"])
    assert cache["hit_rate"] is not None
    assert cache["dirty_fraction"] is not None


def test_ps_admin_cli_stats_and_dump_health_include_cache(capsys):
    from paddle_tpu.ps import EmbeddingShard, ShardServer
    from paddle_tpu.tools import ps_admin
    _mk_cache(capacity=2, step_rows=2)     # guarantees the block exists
    rows = tpe._rand_rows(V, seed=31)
    srv = ShardServer([EmbeddingShard("tb", 0, V,
                                      rows=rows.copy())]).serve_in_thread()
    try:
        rc = ps_admin.main(["stats", "--endpoints", srv.endpoint, "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["shards"][0]["up"]
        assert "hit_rate" in out["hot_cache"]
        rc = ps_admin.main(["dump-health", "--endpoints", srv.endpoint,
                            "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0 and "hit_rate" in doc["hot_cache"]
    finally:
        srv.stop()
