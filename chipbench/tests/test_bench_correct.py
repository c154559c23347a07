"""Whole benchmark runs on the CPU at a tiny size: a sound run is correct,
the control and every fault the cells can have are not, and a stalled
round moves both the rate and the decision tail.

The tiny size keeps the cells' shapes (5 regions, 6 or 40 columns) with
~190 new jobs per round and one day of telemetry. On the CPU the Sinkhorn
runs the XLA loop, whose plan lies up to 0.04 from the optimum at this
size where the chip's Pallas kernel lies closer, so the plan limit here
is 0.15; a cost-blind plan lies far off."""
import time

import numpy as np
import pytest

from chipbench import faults, harness, reference
from repro.policy import pipeline

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
LIMITS = {"plan_gap": 0.15}


def run(cell, seed, seconds=1.0):
    w = harness.workload(BENCH, cell)
    config = harness.config_of(BENCH, w["config"])
    traffic = harness.traffic_of(w["traffic"])
    traffic["jobs_per_day"] = 547200.0
    config["servers_per_region"] = 1690
    config["telemetry"] = dict(config["telemetry"], days=1)
    return harness.run_cell(
        config=config, traffic=traffic, limits=LIMITS,
        metrics=harness.metrics_for(BENCH, cell, False), seed=seed,
        seconds=seconds, traced=False, t_start=time.perf_counter(),
        device=dict(platform="cpu", kind="cpu", count=1))


CELLS = ["waterwise-cell.cadence30", "forecast-cell.cadence30"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run(cell, 3000000011)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["plan_gap"]["value"] > 0
    assert list(res["checks"])[-1] == "plan_gap"
    ends = {m["name"] for m in harness.metrics_for(BENCH, cell, False)}
    assert set(res["metrics"]) == ends


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_control_and_faults_are_not_correct(cell, fault):
    with faults.FAULTS[fault]():
        res = run(cell, 3000000012)
    assert not res["correct"], (fault, res["checks"])


def test_stalled_rounds_move_rate_and_tail(monkeypatch):
    cell = "forecast-cell.cadence30"
    base = run(cell, 3000000013, seconds=1.5)["metrics"]
    schedule = pipeline.PolicyPipeline.schedule
    calls = []

    def stalled(self, jobs, now_s, capacity):
        calls.append(now_s)
        if len(calls) % 4 == 0:
            time.sleep(0.15)
        return schedule(self, jobs, now_s, capacity)

    monkeypatch.setattr(pipeline.PolicyPipeline, "schedule", stalled)
    slow = run(cell, 3000000013, seconds=1.5)["metrics"]
    assert slow["jobs_per_s"]["value"] < 0.9 * base["jobs_per_s"]["value"]
    assert (slow["decision_p95_ms"]["value"]
            > base["decision_p95_ms"]["value"] + 75)


def test_plan_is_priced_on_the_reference_costs():
    cost = np.array([[1.0, 2.0, 9.0], [1.0, 3.0, 9.0], [2.0, 1.0, 9.0]])
    allowed = np.array([[1, 1, 0], [1, 1, 1], [1, 1, 1]], bool)
    inst = reference.Instance(cost=cost, allowed=allowed,
                              capacity=np.array([1, 1, 1]), soft=False,
                              margin=np.zeros((3, 3)), band=np.zeros((3, 3)))

    def gap(plan):
        r = reference.Round(now_s=0.0, E=np.ones(3), t=np.ones(3),
                            home=np.zeros(3, np.int64), size=np.ones(3),
                            tol=np.ones(3), submit=np.zeros(3),
                            capacity=np.ones(3, np.int64),
                            assign=np.array([0, 2, 1]), softened=False,
                            plan=plan)
        return reference.gaps(inst, r)

    opt = 1.0 + 9.0 + 1.0
    best = gap(np.eye(3)[[0, 2, 1]])
    assert best["plan_gap"] == 0.0 and best["served_gap"] == 0.0
    uniform = np.where(allowed, 1.0, 0.0)
    uniform /= uniform.sum(axis=1, keepdims=True)
    want = abs((uniform * cost).sum() - opt) / opt
    assert gap(uniform)["plan_gap"] == pytest.approx(want)
    assert gap(None)["plan_gap"] == float("inf")
    assert gap(np.eye(2))["plan_gap"] == float("inf")
