"""The program's ``serve.FleetLoop``, which the harness takes wherever the
program has it, at the tiny size of ``test_bench_fleets.py``: each fleet's
placements, and the plan and assignment of each of its solves, are bitwise
those of the fleet's ``DecisionLoop`` stepped alone on the one-fleet
program, over one device and over four; a boundary completes where a fleet
has nothing due, goes soft or the wave fails; and once each row bucket has
been seen, no boundary compiles, whichever fleets share a bucket. Whole
runs through the harness, held against its float64 reference, are in
``test_bench_fleets.py``."""
import json
import os
import subprocess
import sys
import threading

import numpy as np

from chipbench import harness, stream as bench_stream
from chipbench.tests.test_bench_fleets import _placed, config_and_traffic

from repro import policy, serve
from repro.core import telemetry
from repro.core.solvers.jax_solver import bucket_for


def _loop(traffic, config, tele, fleet=0, src=None):
    """One fleet's ``DecisionLoop`` as the harness builds it, its policy
    recorded (``loop.stepper.scheduler.calls``)."""
    from repro.serve import DecisionLoop, ServeConfig
    from repro.sim.cluster import Cluster
    from repro.sim.engine import EngineState, EventSimulator, SimConfig
    if src is None:
        src = bench_stream.Stream.from_traffic(traffic, 3000000046, 5,
                                               3600.0, harness.PHASE,
                                               fleet=fleet)
    cap = np.full(5, config["servers_per_region"], np.int64)
    sched = harness.Recorder(policy.build(config["policy"], tele))
    sim = EventSimulator(tele, cap, SimConfig(window_s=30.0))
    loop = DecisionLoop(sim, sched, src,
                        ServeConfig(round_s=30.0, queue_bound=10 ** 9))
    loop.stepper = sim.stepper(sched, state=EngineState(
        now=30.0, pending=[], applied_events=0,
        cluster=Cluster(cap).export_state()))
    return loop


def _tiny(jobs_per_day=2e5):
    config, traffic = config_and_traffic()
    traffic["jobs_per_day"] = jobs_per_day
    return config, traffic, telemetry.generate(**config["telemetry"])


def _same_decisions(loop, ref):
    """Placements, and each call's plan and assignment, bitwise."""
    if _placed(loop) != _placed(ref):
        return False
    calls, ref_calls = (x.stepper.scheduler.calls for x in (loop, ref))
    return len(calls) == len(ref_calls) and all(
        (a.plan is None) == (b.plan is None)
        and (a.plan is None or np.array_equal(a.plan, b.plan))
        and np.array_equal(a.decision.solver.assign, b.decision.solver.assign)
        for a, b in zip(calls, ref_calls) if a.decision.solver is not None)


def test_the_harness_takes_the_programs_fleet_loop():
    assert type(harness.fleet_loop([])) is serve.FleetLoop


def eight_fleets_over_the_devices(boundaries=6):
    """Eight tiny fleets through ``serve.FleetLoop`` beside each stepped
    alone on the one-fleet program; prints what the test checks."""
    import repro.obs as obs
    config, traffic, tele = _tiny()
    loops = [_loop(traffic, config, tele, fleet=i) for i in range(8)]
    alone = [_loop(traffic, config, tele, fleet=i) for i in range(8)]
    fleet = serve.FleetLoop(loops)
    with harness.plan_field(), obs.capture():
        for k in range(1, boundaries + 1):
            fleet.run_round((k + harness.PHASE) * 30.0)
            for loop in alone:
                loop.run_round((k + harness.PHASE) * 30.0)
        s = obs.session()
    print(json.dumps(dict(
        devices=fleet.devices, slots=fleet.slots,
        chips=s["counters"]["solver.batch_chips"]
        / s["spans"]["solver.batch_device"][0],
        batched=s["counters"]["round.batch_solves"],
        same=[_same_decisions(a, b) for a, b in zip(loops, alone)],
        placed=[len(a.stepper.placed) for a in loops])))


def test_eight_fleets_batch_over_four_devices_as_each_alone():
    """On a host split into four devices the wave is shard_mapped two
    fleets per device, and every fleet's plans and placements are bitwise
    those of the fleet stepped alone."""
    from repro.launch import devices as launch_devices
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(harness.ROOT), str(harness.ROOT / "src")]))
    env["XLA_FLAGS"] = launch_devices.merge_xla_flag(
        env.get("XLA_FLAGS"), "--xla_force_host_platform_device_count", 4)
    p = subprocess.run(
        [sys.executable, "-c", "from chipbench.tests.test_bench_fleet_loop "
         "import eight_fleets_over_the_devices as f; f()"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=180)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert (got["devices"], got["slots"], got["chips"]) == (4, 8, 4.0)
    assert got["batched"] >= 8 * 6
    assert all(got["same"]) and min(got["placed"]) > 0


def _within(seconds, fn):
    """Run ``fn`` in a thread; fail if it has not ended after ``seconds``.
    Returns what it returned or raised."""
    out = {}

    def body():
        try:
            out["value"] = fn()
        except BaseException as e:          # noqa: BLE001 — returned
            out["error"] = e

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(timeout=seconds)
    assert not t.is_alive(), "the boundary did not complete"
    return out


def test_a_boundary_completes_when_a_fleet_has_nothing_due_or_goes_soft():
    from repro.serve import ReplayArrivals
    config, traffic, tele = _tiny()
    # Tolerance 0: every job has waited past its tolerance by its first
    # decision, so each of this fleet's rounds goes soft.
    strict = dict(traffic, tolerance=0.0)

    def fleets():
        return [_loop(traffic, config, tele),
                _loop(traffic, config, tele, src=ReplayArrivals([])),
                _loop(strict, config, tele, fleet=2)]

    loops, alone = fleets(), fleets()
    fleet = serve.FleetLoop(loops)
    with harness.plan_field():
        for k in range(1, 4):
            b = (k + harness.PHASE) * 30.0
            assert "error" not in _within(60, lambda: fleet.run_round(b))
            for loop in alone:
                loop.run_round(b)
    calls = [x.stepper.scheduler.calls for x in loops]
    assert calls[0] and all(not c.decision.softened for c in calls[0])
    assert all(c.decision.solver is None for c in calls[1])
    assert calls[2] and all(c.decision.softened for c in calls[2])
    for loop, ref in zip(loops, alone):
        assert _same_decisions(loop, ref)


def test_an_error_in_the_wave_reaches_the_boundary(monkeypatch):
    from repro.core import round as fused_round
    config, traffic, tele = _tiny()
    fleet = serve.FleetLoop([_loop(traffic, config, tele, fleet=i)
                             for i in range(3)])

    def lost(requests, devices=1, slots=None):
        raise RuntimeError("device lost")

    monkeypatch.setattr(fused_round, "fused_round_batch", lost)
    out = _within(60, lambda: fleet.run_round((1 + harness.PHASE) * 30.0))
    assert isinstance(out.get("error"), RuntimeError)


def test_no_boundary_compiles_once_each_bucket_has_been_seen():
    """Three fleets at three rates: after three boundaries every row bucket
    of the next three has been compiled, yet there two fleets share a
    bucket that one fleet alone held before. One batch shape per bucket:
    nothing compiles."""
    config, traffic, tele = _tiny()
    loops = [_loop(dict(traffic, jobs_per_day=rate), config, tele, fleet=i)
             for i, rate in enumerate((1e5, 2e5, 3e5))]
    fleet = serve.FleetLoop(loops)

    def step(k):
        n = [len(x.stepper.scheduler.calls) for x in loops]
        fleet.run_round((k + harness.PHASE) * 30.0)
        rows = [len(x.stepper.scheduler.priced[-1][0])
                for x, n0 in zip(loops, n)
                if len(x.stepper.scheduler.calls) > n0]
        return sorted(bucket_for(m + 1) for m in rows)

    warm = [step(k) for k in range(1, 4)]
    with harness.CompileCounter() as compiles:
        later = [step(k) for k in range(4, 7)]
    assert {b for w in later for b in w} <= {b for w in warm for b in w}
    shared = {(b, w.count(b)) for w in later for b in w}
    assert shared - {(b, w.count(b)) for w in warm for b in w}
    assert compiles.count == 0
