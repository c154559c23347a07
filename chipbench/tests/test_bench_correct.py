"""Whole benchmark runs on the CPU at a tiny size: a sound run is correct,
the control and every fault the cells can have are not, a stalled round
moves both the rate and the decision tail, and ``failed`` counts a job
whose deadline had passed before the loop could first decide it apart
(``readings.late_on_arrival``) while every other late job stays the
program's failure.

The tiny size keeps the cells' shapes (5 regions, 6 or 40 columns) with
~190 new jobs per round and one day of telemetry. On the CPU the Sinkhorn
runs the XLA loop, whose plan lies 0.0001-0.0065 from the optimum at this
size over six seeds of each cell, where the chip's Pallas kernel lies
closer, so the plan limit here is 0.05. A cost-blind plan lies 0.35 off,
and a stale plan, another round's rounded in place of its own, 0.13-0.17
in the forecast cell and 0.40 in the waterwise cell."""
import time

import numpy as np
import pytest

from chipbench import faults, harness, reference, stream as bench_stream
from repro.policy import pipeline

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
LIMITS = {"plan_gap": 0.05}


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
    assert res["readings"]["late_on_arrival"] == 0
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


WATERWISE = "waterwise-cell.cadence30"
TRAFFIC = harness.traffic_of("cadence30")
ROUND_S = TRAFFIC["round_s"]
WARMUP = TRAFFIC["warmup_rounds"]


def plant(monkeypatch, slack_s):
    """Gives the first job that the window's first loop instant decides,
    and that arrived at least 10 s before it, the execution time that
    leaves it ``slack_s`` seconds at its home region when started at that
    instant. Returns a list that holds the planted job once the stream is
    made."""
    make = bench_stream.Stream.from_traffic
    planted = []

    def from_traffic(*a, **k):
        src = make(*a, **k)
        d0 = (WARMUP + 1) * ROUND_S
        job = next(j for j in src.jobs
                   if d0 - ROUND_S < j.submit_time_s <= d0 - 10.0)
        wait = d0 - job.submit_time_s
        exec_s = (wait + slack_s) / job.tolerance
        job.energy_kwh *= exec_s / job.exec_time_s
        job.exec_time_s = exec_s
        planted.append(job)
        return src

    monkeypatch.setattr(bench_stream.Stream, "from_traffic", from_traffic)
    return planted


def test_a_job_late_on_arrival_is_counted_apart_from_failed(monkeypatch):
    planted = plant(monkeypatch, slack_s=-5.0)
    res = run(WATERWISE, 3000000021)
    job, = planted
    assert harness.late_on_arrival(job, ROUND_S)
    assert job.finish_time_s > harness.deadline_s(job)
    assert res["readings"]["late"] == 1
    assert res["readings"]["late_on_arrival"] == 1
    assert res["readings"]["soft_rounds"] >= 1
    assert res["failed"] == 0
    assert res["correct"], res["checks"]


def test_a_reachable_job_made_late_counts_as_failed():
    at_s = (WARMUP + 1) * ROUND_S
    with faults.late_dispatch(at_s):
        res = run(WATERWISE, 3000000022)
    assert res["readings"]["late"] == 1
    assert res["readings"]["late_on_arrival"] == 0
    assert res["failed"] == 1


def test_the_rule_is_pinned_to_the_engine_loop_instants(monkeypatch):
    # One second of slack at the first decision instant: the engine must
    # decide the job at that instant, or it finishes late.
    planted = plant(monkeypatch, slack_s=1.0)
    res = run(WATERWISE, 3000000023)
    job, = planted
    d0 = harness.first_decision_s(job.submit_time_s, ROUND_S)
    assert d0 == (WARMUP + 1) * ROUND_S
    assert d0 - job.submit_time_s == pytest.approx(
        job.tolerance * job.exec_time_s - 1.0)
    assert not harness.late_on_arrival(job, ROUND_S)
    assert job.start_time_s == pytest.approx(d0)
    assert job.finish_time_s <= harness.deadline_s(job)
    assert res["readings"]["late"] == 0
    assert res["failed"] == 0


def test_first_decision_instants():
    assert harness.first_decision_s(0.0, 30.0) == 30.0
    assert harness.first_decision_s(12.5, 30.0) == 30.0
    assert harness.first_decision_s(30.0, 30.0) == 30.0
    assert harness.first_decision_s(30.001, 30.0) == 60.0
    assert harness.first_decision_s(2343.29, 30.0) == 2370.0


@pytest.mark.parametrize("seed,ids", [(3, [27045, 139091]),
                                      (1234567891, [])])
def test_stream_count_over_430_window_rounds(seed, ids):
    """The full-size stream's jobs late on arrival among those first
    decided in the first 430 window rounds, from the stream alone."""
    last = (WARMUP + 430) * ROUND_S
    src = bench_stream.Stream.from_traffic(
        TRAFFIC, seed, 5, span_s=last + ROUND_S, phase=harness.PHASE)
    window = [j for j in src.jobs if WARMUP * ROUND_S
              < harness.first_decision_s(j.submit_time_s, ROUND_S) <= last]
    assert len(window) > 140000
    got = [j.job_id for j in window if harness.late_on_arrival(j, ROUND_S)]
    assert got == ids
