"""The trace reduction, on two ERNIE steps recorded on the chip in PR 23
(data/seq512_two_steps.json.gz: the tuples `xtrace.extract` took from the
`.xplane.pb`, cut to the first two traced blocks) and on hand-made tuples for
what one chip cannot show (collectives)."""
import gzip
import json
import os

import pytest

from benchmark import xtrace
from benchmark.layer_metrics import (attn_ms, collective_exposed_ms,
                                     collective_ms, device_idle, dispatch_ms,
                                     rows_ms, xla_ms)

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "data", "seq512_two_steps.json.gz"),
                   "rt") as f:
        data = json.load(f)
    return xtrace.Reduced(data["events"], data["steps"])


def test_busy_union_and_idle_share(recorded):
    assert recorded.window_s == pytest.approx(0.5797, rel=1e-3)
    assert recorded.busy_s == pytest.approx(0.5637, rel=1e-3)
    # the operations of the XLA Ops line do not nest: union == sum
    total = sum(e[3] for e in recorded.devices["/device:TPU:0"]) * 1e-9
    assert recorded.busy_s == pytest.approx(total, rel=1e-9)
    assert device_idle.read({"trace": recorded}) == pytest.approx(2.758,
                                                                  rel=1e-3)


def test_mosaic_collective_other_split(recorded):
    ctx = {"trace": recorded}
    # 12 forward and 12 backward flash-attention calls a step, nothing else
    assert recorded.kind_calls_per_step("mosaic") == 24
    assert attn_ms.read(ctx) == pytest.approx(28.563, rel=1e-3)
    assert xla_ms.read(ctx) == pytest.approx(253.30, rel=1e-3)
    assert collective_ms.read(ctx) is None
    assert collective_exposed_ms.read(ctx) is None
    assert rows_ms.read(ctx) is None        # no gather/scatter by name here
    per_step = (attn_ms.read(ctx) + xla_ms.read(ctx)) * 2e-3
    assert per_step == pytest.approx(recorded.busy_s, rel=1e-9)
    names = [n for n, _ in recorded.top_ops(10)]
    assert len(names) == 10 and all(len(n) <= 80 for n in names)
    assert recorded.top_ops(1)[0][1] == pytest.approx(0.012933, rel=1e-3)


def test_idle_gaps_are_named_by_the_host_span_they_fall_in(recorded):
    gaps = recorded.idle_gaps(3)
    longest, totals = gaps[:3], dict(gaps[3:])
    # one step a block: the device waits while the next step is dispatched
    assert [g[0] for g in longest[:2]] == ["bench.dispatch"] * 2
    assert longest[0][1] == pytest.approx(8.59e-3, rel=1e-2)
    assert totals["all:bench.dispatch"] > 100 * totals.get("all:bench.wait", 0)
    assert sum(totals.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s, rel=1e-6)
    assert dispatch_ms.read({"trace": recorded}) == pytest.approx(
        (9.620609 + 9.754190) / 2, rel=1e-6)


def test_collectives_on_a_recorded_four_chip_block():
    """dp4_one_block_two_chips.json.gz: the first traced block (4 steps) of
    ernie_base.dp4_seq512 on two of the four chips. GSPMD put four
    synchronous all-reduces in a step and nothing overlaps them."""
    with gzip.open(os.path.join(HERE, "data",
                                "dp4_one_block_two_chips.json.gz"), "rt") as f:
        data = json.load(f)
    r = xtrace.Reduced(data["events"], data["steps"])
    ctx = {"trace": r}
    assert len(r.devices) == 2
    assert r.kind_calls_per_step("collective") == 4
    assert r.kind_calls_per_step("mosaic") == 24
    assert collective_ms.read(ctx) == pytest.approx(5.5035, rel=1e-3)
    assert collective_exposed_ms.read(ctx) == pytest.approx(
        collective_ms.read(ctx), rel=1e-9)
    assert xla_ms.read(ctx) == pytest.approx(231.13, rel=1e-3)
    assert attn_ms.read(ctx) == pytest.approx(28.748, rel=1e-3)
    assert device_idle.read(ctx) == pytest.approx(1.980, rel=1e-3)
    # four steps a block: one gap while the block's first step is dispatched,
    # shorter ones between the queued steps
    gaps = r.idle_gaps(3)
    assert gaps[0][0] == "bench.dispatch" and gaps[1][0] == "bench.wait"
    assert gaps[0][1] > gaps[1][1] > 1e-3


def _ev(name, kind, start_us, dur_us):
    return (name, kind, start_us * 1000, dur_us * 1000)


def test_exposed_collective_time_on_hand_made_tuples():
    # chip 0: a 10 us all-reduce, 4 us of it under a fusion; chip 1: all of
    # its 6 us all-reduce is hidden
    events = {"devices": {
        "/device:TPU:0": [_ev("fusion.1", "xla", 0, 100),
                          _ev("all-reduce-start.1", "collective", 96, 10),
                          _ev("fusion.2", "xla", 106, 50)],
        "/device:TPU:1": [_ev("fusion.1", "xla", 0, 120),
                          _ev("all-reduce-start.1", "collective", 100, 6),
                          _ev("fusion.2", "xla", 120, 36)]},
        "host": [("bench.dispatch", 0, 5_000), ("bench.wait", 5_000, 151_000)]}
    r = xtrace.Reduced(events, steps=1)
    ctx = {"trace": r}
    assert collective_ms.read(ctx) == pytest.approx((10 + 6) / 2 * 1e-3)
    assert collective_exposed_ms.read(ctx) == pytest.approx((6 + 0) / 2 * 1e-3)
    assert r.window_s == pytest.approx(156e-6)
    assert r.busy_s == pytest.approx((156 + 156) / 2 * 1e-6)
    assert r.idle_share == pytest.approx(0.0)
    # a gap in the middle of the wait is named for it
    events["devices"]["/device:TPU:0"][2] = _ev("fusion.2", "xla", 126, 30)
    r = xtrace.Reduced(events, steps=1)
    assert r.idle_gaps(1)[0] == ["bench.wait", pytest.approx(20e-6)]


@pytest.mark.parametrize("text,kind,op", [
    ('%jvp__.12 = (bf16[768,512,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[768,512,128]'
     '{2,1,0}) custom-call(s32[1]{0:T(128)} %constant.249), '
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     "mosaic", "custom-call"),
    ('%custom-call.108 = f32[3072,768]{1,0:T(8,128)S(1)} custom-call(f32[768,'
     '768]{1,0} %slice-done.376), custom_call_target="ConcatBitcast"',
     "xla", "custom-call"),
    ('%fusion.3 = bf16[64,512]{1,0} fusion(bf16[8]{0} %custom-call.5), '
     'kind=kLoop, calls=%fused_computation.1', "xla", "fusion"),
    ("%all-reduce-start.5 = f32[768]{0} all-reduce-start(f32[768]{0} %x), "
     "replica_groups={}", "collective", "all-reduce-start"),
    ("%reduce-scatter.2 = f32[192]{0} reduce-scatter(f32[768]{0} %x)",
     "collective", "reduce-scatter"),
    ("%all-gather-done.1 = f32[768]{0} all-gather-done(f32[192]{0} %y)",
     "collective", "all-gather-done"),
    ("%gather.7 = f32[8,128]{1,0} gather(u16[64,128]{1,0} %t, s32[8]{0} %i)",
     "xla", "gather"),
])
def test_operations_are_classified_by_their_own_opcode(text, kind, op):
    assert xtrace.opcode(text) == op
    assert xtrace.classify(text) == kind
    assert xtrace.label(text).split()[1] == op


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        xtrace.Reduced({"devices": {}, "host": []}, steps=1)
