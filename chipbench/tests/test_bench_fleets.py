"""Several fleets in one run, and the plan each solve carries in its result.

Whole runs on the CPU at the tiny size of ``test_bench_correct.py``: a
configuration that states ``fleets: 2`` is served by two independent
fleets, each held against the reference; every fault in ``faults.FAULTS``
fails such a run; ``fleets: 1`` is the plain path. Then the plan a solve
handed to the host's rounding travels on its own result, per thread, and
the harness's loop over several fleets places what each loop alone would.
"""
import threading
import time

import numpy as np
import pytest

from chipbench import faults, harness, stream as bench_stream
from chipbench.tests.test_bench_correct import BENCH, LIMITS, WATERWISE

from repro import policy
from repro.core import solvers, telemetry
from repro.core.solvers import jax_solver


def config_and_traffic(fleets=None):
    w = harness.workload(BENCH, WATERWISE)
    config = harness.config_of(BENCH, w["config"])
    traffic = harness.traffic_of(w["traffic"])
    traffic["jobs_per_day"] = 547200.0
    config["servers_per_region"] = 1690
    if fleets is not None:
        config["fleets"] = fleets
    return config, traffic


def run(seed, fleets=None, seconds=1.0):
    config, traffic = config_and_traffic(fleets)
    return harness.run_cell(
        config=config, traffic=traffic, limits=LIMITS,
        metrics=harness.metrics_for(BENCH, WATERWISE, False), seed=seed,
        seconds=seconds, traced=False, t_start=time.perf_counter(),
        device=dict(platform="cpu", kind="cpu", count=1))


def test_two_fleets_are_each_held_against_the_reference():
    res = run(3000000041, fleets=2)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["readings"]["fleets"] == 2
    assert res["checks"]["plan_gap"]["value"] > 0
    # The exact optimum's sample is drawn from both fleets' rounds pooled.
    r = res["readings"]
    assert r["rounds_solved_exactly"] == min(
        harness.traffic_of("cadence30")["reference_rounds"],
        r["rounds_checked"] - r["soft_rounds"])


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_every_fault_fails_the_two_fleet_run(fault):
    with faults.FAULTS[fault]():
        res = run(3000000042, fleets=2)
    assert not res["correct"], (fault, res["checks"])


def test_one_fleet_is_the_plain_path(monkeypatch):
    """The same seed over the same twelve window rounds reads the same
    numbers whether the configuration states ``fleets: 1`` or nothing."""
    make = bench_stream.Stream.from_traffic
    rounds = harness.traffic_of("cadence30")["warmup_rounds"] + 12

    def cut(*a, **k):
        src = make(*a, **k)
        src.span_s = (rounds + harness.PHASE) * 30.0 + 1.0
        return src

    monkeypatch.setattr(bench_stream.Stream, "from_traffic", cut)
    plain = run(3000000043, seconds=1e9)
    one = run(3000000043, fleets=1, seconds=1e9)
    assert list(one["checks"]) == list(plain["checks"])
    assert list(one["metrics"]) == list(plain["metrics"])
    assert one["readings"]["rounds_checked"] == 12
    assert one["readings"]["stream_end"] and one["readings"]["fleets"] == 1
    for k in ("plan_gap", "masked", "over_capacity"):
        assert one["checks"][k]["value"] == plain["checks"][k]["value"]
    assert one["attempted"] == plain["attempted"]


def test_fleet_streams_follow_the_seeding_rule():
    traffic = dict(harness.traffic_of("cadence30"), jobs_per_day=2e5)

    def rows(seed, fleet=None):
        kw = {} if fleet is None else dict(fleet=fleet)
        src = bench_stream.Stream.from_traffic(traffic, seed, 5, 1800.0,
                                               harness.PHASE, **kw)
        return [(j.submit_time_s, j.exec_time_s, j.home_region)
                for j in src.jobs]

    seed = 2 ** 31 + 4321
    assert rows(seed, 0) == rows(seed)
    one = rows(seed, 1)
    assert one == rows(seed, 1) and one != rows(seed, 0)
    # Fleet 1's jobs are those of stream seed + 1, dealt apart from it.
    shifted = dict(traffic, stream_seed=traffic["stream_seed"] + 1)
    src = bench_stream.Stream.from_traffic(shifted, seed, 5, 1800.0,
                                           harness.PHASE)
    theirs = [(j.submit_time_s, j.exec_time_s, j.home_region)
              for j in src.jobs]
    assert sorted(one) != sorted(theirs)
    assert sorted(t for t, _, _ in one) == sorted(t for t, _, _ in theirs)
    assert sorted(e for _, e, _ in one) == sorted(e for _, e, _ in theirs)


def _instance(rng, M=40, R=5):
    cost = rng.uniform(1.0, 5.0, (M, R))
    allowed = rng.uniform(size=(M, R)) < 0.8
    allowed[np.arange(M), rng.integers(0, R, M)] = True
    return cost, allowed, np.full(R, M, np.int64)


def test_each_result_carries_the_plan_its_rounding_received(monkeypatch):
    received = []
    rounding = jax_solver._round_to_vertex

    def spy(X, cost, mask, capacity):
        received.append((X, mask))
        return rounding(X, cost, mask, capacity)

    monkeypatch.setattr(jax_solver, "_round_to_vertex", spy)
    finalize = jax_solver._finalize
    rng = np.random.default_rng(3000000044)
    with harness.plan_field():
        results = [solvers.solve(*_instance(rng), backend="fused")
                   for _ in range(3)]
    assert len(received) == 3
    for res, (X, mask) in zip(results, received):
        assert res.plan is X and res.plan_mask is mask
    assert jax_solver._round_to_vertex is spy
    assert jax_solver._finalize is finalize


def test_plans_stay_with_their_solve_across_threads():
    """Solves finalised at once on two threads, as a batcher's flushing
    thread and a fleet's own would, each keep their own plan."""
    rng = np.random.default_rng(3000000045)
    instances = [_instance(rng, M=30 + 5 * i) for i in range(8)]
    results = [None] * len(instances)

    def work(i):
        results[i] = solvers.solve(*instances[i], backend="fused")

    with harness.plan_field():
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(len(instances))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for (cost, allowed, _), res in zip(instances, results):
        assert res.plan.shape == cost.shape
        assert (res.plan_mask == allowed).all()
        np.testing.assert_allclose(res.plan.sum(axis=1), 1.0)


def _loop(traffic, config, tele, fleet=0):
    from repro.serve import DecisionLoop, ServeConfig
    from repro.sim.cluster import Cluster
    from repro.sim.engine import EngineState, EventSimulator, SimConfig
    src = bench_stream.Stream.from_traffic(traffic, 3000000046, 5, 3600.0,
                                           harness.PHASE, fleet=fleet)
    cap = np.full(5, config["servers_per_region"], np.int64)
    sched = policy.build(config["policy"], tele)
    sim = EventSimulator(tele, cap, SimConfig(window_s=30.0))
    loop = DecisionLoop(sim, sched, src,
                        ServeConfig(round_s=30.0, queue_bound=10 ** 9))
    loop.stepper = sim.stepper(sched, state=EngineState(
        now=30.0, pending=[], applied_events=0,
        cluster=Cluster(cap).export_state()))
    return loop


def _placed(loop):
    return [(job.job_id, region, start, finish)
            for job, region, start, finish in loop.stepper.placed]


def test_the_fleet_loop_over_one_loop_places_what_the_loop_places():
    config, traffic = config_and_traffic()
    traffic["jobs_per_day"] = 2e5
    tele = telemetry.generate(**config["telemetry"])
    alone, inner = _loop(traffic, config, tele), _loop(traffic, config, tele)
    fleet = harness.fleet_loop([inner])
    for k in range(1, 9):
        alone.run_round((k + harness.PHASE) * 30.0)
        assert fleet.run_round((k + harness.PHASE) * 30.0) >= 0.0
    assert _placed(alone) and _placed(inner) == _placed(alone)


def test_the_fleet_loop_steps_every_fleet():
    config, traffic = config_and_traffic()
    traffic["jobs_per_day"] = 2e5
    tele = telemetry.generate(**config["telemetry"])
    loops = [_loop(traffic, config, tele, fleet=i) for i in range(3)]
    alone = [_loop(traffic, config, tele, fleet=i) for i in range(3)]
    fleet = harness.fleet_loop(loops)
    for k in range(1, 6):
        fleet.run_round((k + harness.PHASE) * 30.0)
        for loop in alone:
            loop.run_round((k + harness.PHASE) * 30.0)
    for loop, ref in zip(loops, alone):
        assert _placed(loop) and _placed(loop) == _placed(ref)
