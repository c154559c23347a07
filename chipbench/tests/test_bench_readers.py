"""The per-layer readers of the program's spans and counters: each returns
the value computed by hand from a traced window's ``repro.obs`` session,
and None on an untraced run or on a program that has no such span or
counter, or keeps no session."""
import pytest

import repro.obs as obs
from chipbench import harness, trace as bench_trace

# Two answered calls, each inside one round: seconds and counts of the
# window's spans, and its counters' increases.
SESSION = {
    "spans": {"serve.round": (2, 23.5), "policy.schedule": (2, 18.0),
              "solver.solve": (1, 7.0), "solver.fused_round": (1, 7.0),
              "solver.device": (2, 3.0), "solver.finalize": (2, 4.5),
              "forecast.fit": (2, 0.6)},
    "counters": {"solver.d2h_bytes": 1000.0, "host.gc_s": 0.004},
}

WANT = {
    "finalize_ms": 1e3 * 4.5 / 2,
    "device_wait_ms": 1e3 * 3.0 / 2,
    "d2h_bytes": 1000.0 / 2,
    "engine_self_ms": 1e3 * (23.5 - 18.0) / 2,
    "policy_self_ms": 1e3 * (18.0 - 14.0) / 2,
    "gc_ms": 1e3 * 0.004 / 2,
    "refit_ms": 1e3 * 0.6 / 2,
}


def _run(traced=True):
    solves = [dict(rows=10, cols=6, schedule_s=10.0, t0=0.0, t1=10.0),
              dict(rows=10, cols=6, schedule_s=10.0, t0=20.0, t1=30.0)]
    rounds = [harness.RoundStat(12.0, 10.0), harness.RoundStat(11.5, 10.0)]
    trace = bench_trace.Reduction(window_s=31.0, busy_s=1.0, kernel_s={},
                                  top_ops=[], idle_gaps=[])
    return harness.Run(setup_s=1.0, window_s=31.0, rounds=rounds, placed=20,
                       solves=solves, trace=trace if traced else None)


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
    # A traced program that has only the round and solve spans and none
    # of the counters, as before the program carried them.
    older = {"spans": {k: v for k, v in SESSION["spans"].items()
                       if k in ("serve.round", "solver.solve",
                                "solver.fused_round")},
             "counters": {"round.sinkhorn/pallas": 2.0}}
    monkeypatch.setattr(obs, "session", lambda: older)
    assert read(_run()) is None
    monkeypatch.setattr(obs, "session", lambda: None)
    assert read(_run()) is None
    monkeypatch.delattr(obs, "session")          # a program without it
    assert read(_run()) is None


def test_readers_read_a_real_session():
    """Through ``repro.obs`` itself: the readers see what the latest
    enabled session recorded, and only that."""
    obs.counter("solver.d2h_bytes", 7)         # before the window
    with obs.capture(fresh=False):
        for _ in range(2):
            with obs.span("serve.round"):
                with obs.span("policy.schedule"):
                    with obs.span("solver.solve"):
                        with obs.span("solver.finalize"):
                            pass
            obs.counter("solver.d2h_bytes", 500)
        s = obs.session()
    run = _run()
    assert harness.metric_reader("d2h_bytes")(run) == 500.0
    fin = harness.metric_reader("finalize_ms")(run)
    assert fin == pytest.approx(1e3 * s["spans"]["solver.finalize"][1] / 2)
    assert harness.metric_reader("engine_self_ms")(run) >= 0.0
    assert harness.metric_reader("refit_ms")(run) is None


def test_new_metrics_are_declared_for_their_cells():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        m = per_layer[name]
        assert m["moves"] == "jobs_per_s" and m["better"] == "lower"
        cells = ["forecast-cell.cadence30"] if name == "refit_ms" else [
            "waterwise-cell.cadence30", "forecast-cell.cadence30"]
        assert m["workloads"] == cells
