"""The readers of the fleets cell's per-layer metrics: each returns the
value computed by hand from a traced window's ``repro.obs`` session, and
None on an untraced run or on a program that steps the fleets in turn and
so has none of the batch's spans and counters."""
import pytest

import repro.obs as obs
from chipbench import harness, trace as bench_trace

# Two fleets' solves batched in one wave per boundary over four chips: two
# boundaries, each wave one dispatch of two slots at bucket 16.
SESSION = {
    "spans": {"serve.round": (4, 23.5), "policy.schedule": (4, 18.0),
              "serve.fleet_round": (2, 25.0),
              "solver.round_batch": (2, 0.05),
              "solver.batch_device": (2, 0.04)},
    "counters": {"solver.batch_rows": 22.0,
                 "solver.batch_padded_rows": 64.0,
                 "solver.batch_sinkhorn_iters": 720.0,
                 "round.batch_solves": 2.0, "solver.batch_chips": 8.0,
                 "round.sinkhorn/pallas": 2.0},
}
KERNEL_S = 1e-6
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}

WANT = {
    "fleet_round_ms": 1e3 * 25.0 / 2,
    "batch_device_ms": 1e3 * 0.04 / 2,
    "batch_occupancy": 100.0 * 22.0 / 64.0,
    # 360 iterations x 11 x 6 elements x 12 operations per call, a quarter
    # of it on each chip, against the kernel's mean seconds per chip; the
    # operations bound it.
    "batch_sinkhorn_roofline": 100.0 * (2 * 360 * 11 * 6 * 12 / 4 / 197e12)
    / KERNEL_S,
}


def _run(traced=True):
    solves = [dict(rows=10, cols=6, schedule_s=10.0, t0=0.0, t1=10.0),
              dict(rows=10, cols=6, schedule_s=10.0, t0=20.0, t1=30.0)]
    rounds = [harness.RoundStat(12.0, 10.0), harness.RoundStat(11.5, 10.0)]
    trace = bench_trace.Reduction(
        window_s=31.0, busy_s=1.0,
        kernel_s={"sinkhorn_iteration_pallas": KERNEL_S}, top_ops=[],
        idle_gaps=[])
    return harness.Run(setup_s=1.0, window_s=31.0, rounds=rounds, placed=20,
                       solves=solves, trace=trace if traced else None,
                       peaks=PEAKS)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_the_hand_computed_value(name, monkeypatch):
    monkeypatch.setattr(obs, "session", lambda: SESSION)
    got = harness.metric_reader(name)(_run())
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_none_without_its_source(name, monkeypatch):
    read = harness.metric_reader(name)
    monkeypatch.setattr(obs, "session", lambda: SESSION)
    assert read(_run(traced=False)) is None
    # The fleets stepped in turn: each fleet's own rounds and solves, no
    # wave.
    in_turn = {"spans": {k: SESSION["spans"][k]
                         for k in ("serve.round", "policy.schedule")},
               "counters": {"round.sinkhorn/pallas": 4.0}}
    monkeypatch.setattr(obs, "session", lambda: in_turn)
    assert read(_run()) is None
    monkeypatch.setattr(obs, "session", lambda: None)
    assert read(_run()) is None


def test_fleet_metrics_are_declared_for_the_fleets_cell():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        m = per_layer[name]
        assert m["moves"] == "jobs_per_s"
        assert m["workloads"] == ["waterwise-fleets8.cadence30"]
    assert {per_layer[n]["unit"] for n in ("batch_occupancy",
                                           "batch_sinkhorn_roofline")} == {
        "%"}
