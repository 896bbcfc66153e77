"""HBM budget planner: candidate ladder, estimates, structured errors.

The planner compiles candidates against shape structs and reads XLA's
`memory_analysis()` — exact per-device numbers even on the fake-8-device
CPU mesh, which is what makes these tests real: stage3 genuinely shrinks
the measured argument bytes here."""
import json
import subprocess

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import planner

from test_zero_sharding import OPTS, _build


def _model():
    main, _startup, feed, loss = _build(OPTS["adam"])
    return main, feed, loss.name


# -- estimation ------------------------------------------------------------

def test_measured_estimates_shrink_with_stage3():
    main, feed, loss_name = _model()
    p0 = planner.estimate_plan(planner.Plan(0, "none", 1), main, feed,
                               loss_name)
    p3 = planner.estimate_plan(planner.Plan(3, "none", 1), main, feed,
                               loss_name)
    assert p0.source == "measured" and p3.source == "measured"
    assert p3.est_bytes_per_device < p0.est_bytes_per_device


def test_unconstrained_returns_baseline_without_compiling():
    main, feed, loss_name = _model()
    plan = planner.plan_for(main, feed, loss_name, budget_bytes=None)
    assert (plan.stage, plan.remat, plan.microbatch) == (0, "none", 1)
    assert plan.source == "unconstrained" and plan.fits


def test_ladder_escalates_to_first_fit():
    main, feed, loss_name = _model()
    p0 = planner.estimate_plan(planner.Plan(0, "none", 1), main, feed,
                               loss_name)
    p1 = planner.estimate_plan(planner.Plan(1, "none", 1), main, feed,
                               loss_name)
    assert p1.est_bytes_per_device < p0.est_bytes_per_device
    mid = (p0.est_bytes_per_device + p1.est_bytes_per_device) // 2
    plan = planner.plan_for(main, feed, loss_name, budget_bytes=mid)
    assert plan.stage >= 1 and plan.fits
    assert plan.est_bytes_per_device <= mid


def test_no_fit_raises_structured_error():
    main, feed, loss_name = _model()
    with pytest.raises(planner.HbmBudgetError) as ei:
        planner.plan_for(main, feed, loss_name, budget_bytes=64)
    err = ei.value
    assert err.plan is not None                      # best-found attached
    assert err.plan.est_bytes_per_device is not None
    assert len(err.candidates) >= 6                  # whole ladder walked
    # best-found is the min-estimate candidate
    assert err.plan.est_bytes_per_device == min(
        p.est_bytes_per_device for p in err.candidates
        if p.est_bytes_per_device is not None)
    assert "best found" in str(err)


def test_microbatch_candidates_respect_divisibility():
    cands = planner.default_candidates(batch=12, dp=4)
    ks = [p.microbatch for p in cands if p.microbatch > 1]
    # 12/2=6 not divisible by dp=4; 12/4=3 not divisible; 12/8 not integer
    assert ks == []
    cands = planner.default_candidates(batch=32, dp=4)
    assert [p.microbatch for p in cands if p.microbatch > 1] == [2, 4, 8]


# -- observability ---------------------------------------------------------

def test_plan_recorded_in_registry_and_flight():
    from paddle_tpu.observability.flight import (_collect_sections,
                                                 get_flight_recorder)
    from paddle_tpu.observability.registry import get_registry

    main, feed, loss_name = _model()
    plan = planner.plan_for(main, feed, loss_name, budget_bytes=1 << 30)
    snap = get_registry().snapshot(deep=True)
    assert snap["planner/chosen_stage"] == plan.stage
    assert snap["planner/chosen_microbatch"] == plan.microbatch
    assert snap["planner/est_bytes_per_device"] == plan.est_bytes_per_device
    assert snap["planner/budget_bytes"] == float(1 << 30)
    sec = _collect_sections()["hbm_plan"]
    assert sec["chosen"]["stage"] == plan.stage
    assert any(c["fits"] for c in sec["candidates"])
    evs = [e for e in get_flight_recorder().contents()["events"]
           if e["message"] == "hbm_plan"]
    assert evs and evs[-1]["stage"] == plan.stage


def test_guard_converts_oom_to_budget_error():
    main, feed, loss_name = _model()
    plan = planner.plan_for(main, feed, loss_name, budget_bytes=1 << 30)
    with pytest.raises(planner.HbmBudgetError) as ei:
        with planner.guard("test/guard", plan=plan):
            raise RuntimeError("RESOURCE_EXHAUSTED: 2.5G over budget")
    assert ei.value.plan is plan
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    assert isinstance(ei.value.__cause__, RuntimeError)


def test_guard_passes_non_oom_through():
    with pytest.raises(ValueError):
        with planner.guard("test/guard"):
            raise ValueError("not a memory problem")


# -- CLI -------------------------------------------------------------------

def test_hbm_plan_cli_json(capsys):
    from paddle_tpu.tools import hbm_plan

    code = hbm_plan.main(["--model", "mlp", "--batch", "8",
                          "--budget", "1e9", "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0
    assert out["fits"] is True
    assert out["chosen"]["source"] == "measured"
    assert out["chosen"]["est_bytes_per_device"] > 0


def test_hbm_plan_cli_no_fit_exit_code(capsys):
    from paddle_tpu.tools import hbm_plan

    code = hbm_plan.main(["--model", "mlp", "--budget", "64", "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 2
    assert out["fits"] is False
    assert out["chosen"] is not None  # best-found plan still reported
    assert len(out["candidates"]) >= 6
