"""Observability layer: exact histogram quantiles, associative snapshot
merge, span nesting in the exported Chrome trace, warning counters on the
degenerate paths, the report CLI, and the disabled-mode pin (obs off and
obs on produce bit-identical engine records)."""
import json
import sys
import threading
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.obs as obs
from repro.core import solvers
from repro.obs.metrics import (HIST_BASE, Histogram, MetricsRegistry,
                               bucket_bounds, bucket_index, merge_snapshots)


# ---------------------------------------------------------------------------
# histogram quantiles
# ---------------------------------------------------------------------------

def test_quantiles_exact_vs_numpy():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=2.0, size=997)
    h = Histogram()
    for v in vals:
        h.observe(v)
    for q in (0, 10, 50, 90, 95, 99, 100):
        assert h.quantile(q) == pytest.approx(np.percentile(vals, q),
                                              rel=0, abs=1e-12)
    assert h.count == len(vals)
    assert h.mean == pytest.approx(vals.mean())


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(min_value=1e-9, max_value=1e6,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=300),
       st.floats(min_value=0.0, max_value=100.0))
def test_quantiles_exact_property(vals, q):
    h = Histogram()
    for v in vals:
        h.observe(v)
    assert h.quantile(q) == pytest.approx(np.percentile(vals, q),
                                          rel=1e-12, abs=1e-15)


def test_quantile_bounded_error_after_overflow():
    """Once the sample buffer drops, bucket quantiles stay within the
    bucket base's relative error of the exact answer."""
    rng = np.random.default_rng(1)
    vals = rng.lognormal(mean=0.0, sigma=1.5, size=2000)
    h = Histogram(max_samples=100)           # force overflow
    for v in vals:
        h.observe(v)
    assert h.samples is None
    for q in (50, 95, 99):
        exact = np.percentile(vals, q)
        assert h.quantile(q) == pytest.approx(exact, rel=HIST_BASE - 1.0)


def test_bucket_geometry():
    for v in (1e-6, 0.37, 1.0, 42.0):
        lo, hi = bucket_bounds(bucket_index(v))
        assert lo < v <= hi or v <= lo  # <=: values clamp at the tiny floor
    assert bucket_index(0.0) == bucket_index(-5.0)   # non-positive clamps


# ---------------------------------------------------------------------------
# snapshot / merge
# ---------------------------------------------------------------------------

def _registry_with(vals, counters=(), gauges=()):
    r = MetricsRegistry()
    for v in vals:
        r.observe("lat", v)
    for name, n in counters:
        r.counter(name, n)
    for name, v, w in gauges:
        r.gauge(name, v, w)
    return r


def test_merge_is_associative():
    rng = np.random.default_rng(2)
    parts = [rng.lognormal(size=40) for _ in range(3)]
    snaps = [_registry_with(p, counters=[("n", len(p))],
                            gauges=[("g", p.mean(), len(p))]).snapshot()
             for p in parts]
    ab_c = merge_snapshots([merge_snapshots(snaps[:2]), snaps[2]])
    a_bc = merge_snapshots([snaps[0], merge_snapshots(snaps[1:])])
    assert ab_c["counters"] == a_bc["counters"]
    assert ab_c["gauges"]["g"]["weight"] == a_bc["gauges"]["g"]["weight"]
    assert ab_c["gauges"]["g"]["value"] == pytest.approx(
        a_bc["gauges"]["g"]["value"])
    ha, hb = ab_c["hists"]["lat"], a_bc["hists"]["lat"]
    assert ha["counts"] == hb["counts"] and ha["count"] == hb["count"]
    assert sorted(ha["samples"]) == sorted(hb["samples"])


def test_merged_quantile_equals_pooled():
    rng = np.random.default_rng(3)
    parts = [rng.lognormal(size=50) for _ in range(4)]
    merged = merge_snapshots(
        [_registry_with(p).snapshot() for p in parts])
    reg = MetricsRegistry()
    reg.merge(merged)
    pooled = np.concatenate(parts)
    assert reg.hists["lat"].quantile(95) == pytest.approx(
        np.percentile(pooled, 95), abs=1e-12)


def test_gauge_merge_is_weighted_mean():
    reg = MetricsRegistry()
    reg.gauge("depth", 10.0, weight=1.0)
    reg.merge({"gauges": {"depth": {"value": 40.0, "weight": 3.0}},
               "counters": {}, "hists": {}})
    g = reg.gauges["depth"]
    assert g.weight == 4.0
    assert g.value == pytest.approx((10.0 * 1 + 40.0 * 3) / 4)


def test_snapshot_is_json_round_trippable():
    snap = _registry_with([0.1, 0.2], counters=[("c", 2)],
                          gauges=[("g", 1.0, 1.0)]).snapshot()
    reg = MetricsRegistry()
    reg.merge(json.loads(json.dumps(snap)))
    assert reg.hists["lat"].count == 2
    assert reg.counters["c"].value == 2


# ---------------------------------------------------------------------------
# spans and the exported trace
# ---------------------------------------------------------------------------

def test_span_nesting_in_exported_trace(tmp_path):
    path = tmp_path / "t.trace.jsonl"
    with obs.capture(trace_path=str(path)):
        with obs.span("outer", kind="round"):
            with obs.span("inner.a"):
                obs.annotate(jobs=3)
            with obs.span("inner.b"):
                pass
    events = obs.read_trace(str(path))
    assert obs.validate_events(events) == []
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(spans) == {"outer", "inner.a", "inner.b"}
    out, a, b = spans["outer"], spans["inner.a"], spans["inner.b"]
    # containment: children inside the parent interval, a before b
    assert out["ts"] <= a["ts"] and a["ts"] + a["dur"] <= out["ts"] + out["dur"]
    assert out["ts"] <= b["ts"] and b["ts"] + b["dur"] <= out["ts"] + out["dur"]
    assert a["ts"] + a["dur"] <= b["ts"]
    assert a["args"]["jobs"] == 3            # annotate hit the open span
    assert out["args"]["kind"] == "round"


def test_span_observes_histogram():
    with obs.capture() as reg:
        with obs.span("stage"):
            pass
        with obs.span("stage"):
            pass
        assert reg.hists["stage"].count == 2


def test_timed_measures_when_disabled():
    assert not obs.enabled()
    with obs.timed("anything") as t:
        sum(range(1000))
    assert t.elapsed_s > 0.0
    assert "anything" not in obs.registry().hists   # no metric recorded


def test_disabled_span_is_shared_noop():
    assert not obs.enabled()
    s1, s2 = obs.span("a"), obs.span("b", x=1)
    assert s1 is s2                          # the whole disabled-mode cost


def test_capture_restores_and_folds():
    obs.reset()
    with obs.capture():
        obs.observe("inner", 1.0)
    assert not obs.enabled()
    assert obs.registry().hists["inner"].count == 1   # folded out
    with obs.capture(fold=False):
        obs.observe("dropped", 1.0)
    assert "dropped" not in obs.registry().hists
    obs.reset()


# ---------------------------------------------------------------------------
# warning counters on the degenerate paths
# ---------------------------------------------------------------------------

def test_bucket_overflow_warn_counter():
    from repro.core.solvers import jax_solver
    obs.reset()
    rows = jax_solver.BUCKETS[-1] + 1
    # The overflow warning fires once per ad-hoc size; re-arm in case an
    # earlier test already overflowed into the same bucket.
    jax_solver._OVERFLOW_WARNED.discard(2 * jax_solver.BUCKETS[-1])
    before = obs.counter_value("warn/solver.bucket_overflow")
    with pytest.warns(RuntimeWarning, match="padded bucket"):
        warnings.simplefilter("always")
        b = jax_solver.bucket_for(rows)
    assert b >= rows
    assert obs.counter_value("warn/solver.bucket_overflow") == before + 1
    # ...and is deduplicated on repeat overflows of that size.
    jax_solver.bucket_for(rows)
    assert obs.counter_value("warn/solver.bucket_overflow") == before + 1


def test_forecaster_fallback_warn_counter():
    from repro.forecast import make_forecaster
    obs.reset()
    f = make_forecaster("learned", train_steps=2, seed=0)
    with pytest.warns(RuntimeWarning, match="seasonal-naive"):
        warnings.simplefilter("always")
        f.fit(np.abs(np.random.default_rng(0).normal(size=(6, 3))) + 1.0)
    assert obs.counter_value("warn/forecast.fallback_seasonal_naive") >= 1


def test_degenerate_wan_warn_counter(monkeypatch):
    from repro.core import telemetry
    bw = telemetry.WAN_BW_GBPS.copy()
    bw[0, 1] = bw[1, 0] = 0.0                # knock out one WAN link
    monkeypatch.setattr(telemetry, "WAN_BW_GBPS", bw)
    obs.reset()
    with pytest.warns(RuntimeWarning, match="WAN"):
        warnings.simplefilter("always")
        tele = telemetry.generate(days=1, seed=0)
    assert obs.counter_value("warn/telemetry.degenerate_wan") >= 1
    assert (tele.bw_gbps[0, 1] > 0.0).all()  # patched, not left at zero


# ---------------------------------------------------------------------------
# disabled-mode pin: obs on vs off is bit-identical engine output
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("spec", ["waterwise[backend=jax]",
                                  "waterwise[backend=fused]"])
def test_records_bit_identical_obs_on_vs_off(tmp_path, spec):
    from repro.experiments.plan import Cell
    from repro.experiments.runner import run_cell
    from repro.experiments.scenario import parse_scenario
    from repro import policy

    cell = Cell(parse_scenario("diurnal[days=0.05,jobs_per_day=2000]"),
                policy.as_spec(spec), 0)
    assert not obs.enabled()
    off = run_cell(cell, return_result=True)
    with obs.capture(trace_path=str(tmp_path / "cell.trace.jsonl")):
        on = run_cell(cell, return_result=True)

    def key(r):
        return (r.job.job_id, r.region, r.start_s, r.finish_s,
                r.carbon_g, r.water_l)

    assert [key(r) for r in off["_result"]["records"]] \
        == [key(r) for r in on["_result"]["records"]]
    for col in ("carbon_kg", "water_kl", "violation_pct", "utilization"):
        assert off[col] == on[col]


# ---------------------------------------------------------------------------
# spans inside the solve, their identity, the runtime hooks
# ---------------------------------------------------------------------------

def _spans(path):
    events = obs.read_trace(str(path))
    assert obs.validate_events(events) == []
    return [e for e in events if e["ph"] == "X"]


def _program_spans(path):
    """The spans of the program's own sites (no GC or compile events)."""
    return [e for e in _spans(path)
            if e["name"] not in ("host.gc", "jax.compile")]


def _children(spans, parent):
    return [e["name"] for e in spans
            if e["args"]["parent"] == parent["args"]["sid"]]


def _assert_solve_tree(spans, root_name):
    sids = [e["args"]["sid"] for e in spans]
    assert len(set(sids)) == len(sids)
    by = {e["name"]: e for e in spans}
    root = by[root_name]
    assert root["args"]["parent"] is None
    assert _children(spans, root) == ["solver.pack", "solver.device",
                                      "solver.finalize"]
    assert _children(spans, by["solver.finalize"]) == [
        "solver.round_vertex", "solver.polish"]
    polish = by["solver.polish"]["args"]
    assert polish["passes"] >= 1 and polish["moves"] >= 0 \
        and polish["swaps"] >= 0
    for e in spans:                        # children inside their parent
        if e["args"]["parent"] is not None:
            p = next(q for q in spans
                     if q["args"]["sid"] == e["args"]["parent"])
            assert p["ts"] <= e["ts"] + 1e-3
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3


def test_fused_solve_spans_nest_with_identity(tmp_path):
    from repro.core import round as fused_round
    rng = np.random.default_rng(0)
    cost = rng.random((20, 6))
    with obs.capture(trace_path=str(tmp_path / "t.jsonl")):
        res = fused_round.fused_solve(cost, np.ones((20, 6), bool),
                                      np.full(6, 10), sinkhorn_impl="pallas",
                                      interpret=True)
    assert res.feasible
    _assert_solve_tree(_program_spans(tmp_path / "t.jsonl"), "solver.solve")


def _temporal_case(M, S=8, R=5):
    from repro.core import footprint, problem, telemetry
    tele = telemetry.generate(days=1, seed=0)
    rng = np.random.default_rng(M)
    jobs = [problem.Job(job_id=i, home_region=i % R, submit_time_s=0.0,
                        exec_time_s=600.0 + 10 * i, energy_kwh=0.05,
                        tolerance=4.0) for i in range(M)]
    cap = np.full(R, max(2, M // R + 1))
    snap = tele.at(0.0)
    server = footprint.m5_metal()
    inst = problem.build(jobs, tele, 0.0, cap, server, snap=snap)
    shape = (M, S, R)
    return (inst, 0.0, rng.random(shape) * 300 + 50,
            rng.random(shape) * 2 + 0.5, rng.random(shape) + 0.2,
            snap["pue"], snap["wsf"], np.arange(S) * 1800.0, server, 0.5,
            0.5)


def test_fused_temporal_round_spans_nest_with_identity(tmp_path):
    from repro.core import round as fused_round
    with obs.capture(trace_path=str(tmp_path / "t.jsonl")):
        *_, res = fused_round.fused_temporal_round(
            *_temporal_case(12), sinkhorn_impl="pallas", interpret=True)
    assert res.feasible
    _assert_solve_tree(_program_spans(tmp_path / "t.jsonl"),
                       "solver.fused_round")


def test_ssp_repair_span_only_when_taken(tmp_path, monkeypatch):
    from repro.core.solvers import jax_solver
    rng = np.random.default_rng(1)
    cost, allowed, cap = rng.random((15, 5)), np.ones((15, 5), bool), \
        np.full(5, 4)
    monkeypatch.setattr(jax_solver, "_round_to_vertex",
                        lambda X, c, m, k: np.full(len(X), -1))
    with obs.capture(trace_path=str(tmp_path / "t.jsonl")):
        res = solvers.solve(cost, allowed, cap, backend="fused")
    assert res.feasible
    spans = _program_spans(tmp_path / "t.jsonl")
    fin = next(e for e in spans if e["name"] == "solver.finalize")
    assert _children(spans, fin) == ["solver.round_vertex",
                                     "solver.ssp_repair", "solver.polish"]


def _d2h(fn):
    before = obs.counter_value("solver.d2h_bytes")
    fn()
    return obs.counter_value("solver.d2h_bytes") - before


@pytest.mark.parametrize("M,N", [(20, 6), (300, 6), (511, 40), (40, 5)])
def test_d2h_bytes_per_fused_solve(M, N):
    """Counted with obs off: the normalized costs and the plan, each
    (bucket - 1) x N float32 rows."""
    from repro.core.round import _pad_rows
    rng = np.random.default_rng(M)
    cap = np.full(N, M // N + 2)
    got = _d2h(lambda: solvers.solve(rng.random((M, N)),
                                     np.ones((M, N), bool), cap,
                                     backend="fused"))
    bucket, _ = _pad_rows(M)
    assert got == 2 * (bucket - 1) * N * 4


@pytest.mark.parametrize("warm", [False, True])
def test_d2h_bytes_per_fused_temporal_round(warm):
    from repro.core import round as fused_round
    from repro.core.round import _pad_rows
    M, cols = 12, 8 * 5
    ws = fused_round.SinkhornWarmStart() if warm else None
    got = _d2h(lambda: fused_round.fused_temporal_round(
        *_temporal_case(M), warm_start=ws))
    bucket, _ = _pad_rows(M)
    # Cn and X, the 4-byte scale; the warm path also the potentials g and
    # the int32 iteration count.
    want = 2 * (bucket - 1) * cols * 4 + 4 + (cols * 4 + 4 if warm else 0)
    assert got == want


def test_d2h_bytes_of_the_jax_backend_plan():
    from repro.core.solvers.jax_solver import bucket_for
    rng = np.random.default_rng(2)
    got = _d2h(lambda: solvers.solve(rng.random((9, 5)),
                                     np.ones((9, 5), bool), np.full(5, 3),
                                     backend="jax"))
    assert got == bucket_for(10) * 5 * 4     # the padded plan, dummy row in


def test_fetch_counts_what_it_copies():
    import jax.numpy as jnp
    from repro.core.solvers import jax_solver
    out = {}
    got = _d2h(lambda: out.update(v=jax_solver.fetch(
        (jnp.ones((3, 4)), jnp.zeros(5, jnp.int32), jnp.float32(2.0)))))
    assert got == sum(np.asarray(a).nbytes for a in out["v"]) == 48 + 20 + 4
    assert all(isinstance(a, np.ndarray) for a in out["v"])


def test_gc_hook_exists_only_while_enabled(tmp_path):
    import gc
    assert obs._gc_hook not in gc.callbacks
    with obs.capture(trace_path=str(tmp_path / "t.jsonl")):
        assert obs._gc_hook in gc.callbacks
        with obs.span("outer"):
            gc.collect()
        gc_s = obs.counter_value("host.gc_s")
    assert obs._gc_hook not in gc.callbacks
    assert gc_s > 0
    spans = _spans(tmp_path / "t.jsonl")
    outer = next(e for e in spans if e["name"] == "outer")
    assert "host.gc" in _children(spans, outer)
    before = obs.counter_value("host.gc_s")
    gc.collect()
    assert obs.counter_value("host.gc_s") == before


def test_fresh_shape_emits_one_compile_span(tmp_path):
    import jax
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    with obs.capture(trace_path=str(tmp_path / "t.jsonl")):
        c0 = obs.counter_value("jit/compiles")
        with obs.span("outer"):
            f(np.ones(13, np.float32))
        c1 = obs.counter_value("jit/compiles")
        f(np.ones(13, np.float32))             # cached: nothing compiles
        assert obs.counter_value("jit/compiles") == c1
    assert c1 - c0 == 1
    spans = _spans(tmp_path / "t.jsonl")
    outer = next(e for e in spans if e["name"] == "outer")
    compiles = [e for e in spans if e["name"] == "jax.compile"]
    assert len(compiles) == 1
    assert compiles[0]["args"]["parent"] == outer["args"]["sid"]
    assert outer["ts"] <= compiles[0]["ts"] + 1e-3
    # the counters stay live with obs off
    before = obs.counter_value("jit/compiles")
    f(np.ones(17, np.float32))
    assert obs.counter_value("jit/compiles") == before + 1


def test_trace_writer_keeps_events_until_its_buffer_fills(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(obs.TraceWriter, "BUFFER_EVENTS", 5)
    path = tmp_path / "t.jsonl"
    with obs.capture(trace_path=str(path)):
        for i in range(12):
            with obs.span("step", i=i):
                pass
        written = obs.read_trace(str(path))
        emitted = obs.tracer().events_written
        assert len(written) % 5 == 0 and emitted - 5 < len(written) <= emitted
    events = obs.read_trace(str(path))
    assert obs.validate_events(events) == [] and len(events) == emitted
    assert [e["args"]["i"] for e in events if e["ph"] == "X"] == list(
        range(12))


def test_engine_trace_has_no_queue_counter(tmp_path):
    from repro.core import telemetry
    from repro.sim import EventSimulator, borg_trace
    from repro import policy
    tele = telemetry.generate(days=1, seed=0)
    jobs = borg_trace(days=0.02, seed=3, tolerance=0.5)
    with obs.capture(trace_path=str(tmp_path / "t.jsonl")):
        EventSimulator(tele, np.full(tele.num_regions, 40)).run(
            jobs, policy.build("waterwise", tele))
    events = obs.read_trace(str(tmp_path / "t.jsonl"))
    assert any(e["name"] == "engine.round" for e in events)
    assert not any(e["name"] == "engine/queue" for e in events)


def test_session_records_the_latest_enabled_window(tmp_path):
    obs.counter("t/session", 5)                # before: not counted
    path = tmp_path / "t.jsonl"
    with obs.capture(trace_path=str(path), fresh=False):
        obs.counter("t/session", 2)
        for _ in range(3):
            with obs.span("t.outer"):
                with obs.span("t.inner"):
                    sum(range(100))
        live = obs.session()
    obs.counter("t/session", 100)              # after: not counted
    s = obs.session()
    assert s["counters"]["t/session"] == 2 and "host.gc_s" in s["counters"]
    assert live["spans"]["t.outer"] == s["spans"]["t.outer"]
    events = [e for e in obs.read_trace(str(path)) if e["ph"] == "X"]
    for name in ("t.outer", "t.inner"):
        n, seconds = s["spans"][name]
        durs = [e["dur"] for e in events if e["name"] == name]
        assert n == len(durs) == 3
        assert seconds == pytest.approx(1e-6 * sum(durs), abs=1e-5)
    with obs.capture(fresh=False):             # a new session starts empty
        assert "t.outer" not in obs.session()["spans"]


def test_served_round_nests_the_schedule_and_its_solves(tmp_path):
    """Every solve span of a served round lies in a ``policy.schedule``
    span, and every ``policy.schedule`` in a ``serve.round``: what the
    benchmark's ``engine_self_ms`` and ``policy_self_ms`` subtract."""
    from repro.core import telemetry
    from repro.policy.pipeline import forecast_pipeline
    from repro.serve import DecisionLoop, ReplayArrivals, ServeConfig
    from repro.sim import borg_trace
    from repro.sim.engine import EventSimulator, SimConfig
    tele = telemetry.generate(days=1, seed=0)
    jobs = borg_trace(days=0.02, seed=3, tolerance=4.0)
    loop = DecisionLoop(
        EventSimulator(tele, np.full(tele.num_regions, 40), SimConfig()),
        forecast_pipeline(tele, forecaster="oracle", risk=0.0,
                          defer_eps=1e-4, backend="fused"),
        ReplayArrivals(jobs), ServeConfig(round_s=300.0,
                                          queue_bound=1 << 30))
    with obs.capture(trace_path=str(tmp_path / "t.jsonl")):
        loop.run(0.02 * 86400.0, drain=False)   # rounds only, no drain
    spans = _spans(tmp_path / "t.jsonl")
    by_sid = {e["args"]["sid"]: e for e in spans}

    def ancestors(e):
        while e["args"]["parent"] is not None:
            e = by_sid[e["args"]["parent"]]
            yield e["name"]

    solves = [e for e in spans
              if e["name"] in ("solver.solve", "solver.fused_round")]
    calls = [e for e in spans if e["name"] == "policy.schedule"]
    assert solves and calls
    assert all("policy.schedule" in ancestors(e) for e in solves)
    assert all("serve.round" in ancestors(e) for e in calls)
    rounds = [e["args"]["round"] for e in spans if e["name"] == "serve.round"]
    assert rounds == sorted(rounds) and len(set(rounds)) == len(rounds)


def test_spans_and_counters_are_per_thread_under_many_threads(tmp_path):
    """Threads that open nested spans and bump one counter at once: each
    span's parent is its own thread's enclosing span, every annotation
    lands on its own thread's span, no span stays open on any stack, and
    the counter adds up."""
    n_threads, rounds = 12, 40
    path = tmp_path / "t.jsonl"
    start = threading.Barrier(n_threads)
    left = {}

    def work(i):
        start.wait(timeout=30)
        for r in range(rounds):
            with obs.span("t.outer", thread=i, r=r):
                for k in range(3):
                    with obs.span("t.inner", k=k):
                        obs.counter("t/threads")
                        obs.annotate(thread=i, r=r)
        left[i] = len(obs._stack())

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.capture(trace_path=str(path), fresh=False):
            before = obs.counter_value("t/threads")
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            counted = obs.counter_value("t/threads") - before
    finally:
        sys.setswitchinterval(switch)
    assert counted == n_threads * rounds * 3
    assert left == dict.fromkeys(range(n_threads), 0)
    spans = _spans(path)
    by_sid = {e["args"]["sid"]: e for e in spans}
    outer = [e for e in spans if e["name"] == "t.outer"]
    inner = [e for e in spans if e["name"] == "t.inner"]
    assert len(outer) == n_threads * rounds and len(inner) == 3 * len(outer)
    assert all(e["args"]["parent"] is None for e in outer)
    for e in inner:
        parent = by_sid[e["args"]["parent"]]
        assert parent["name"] == "t.outer" and parent["tid"] == e["tid"]
        assert (e["args"]["thread"], e["args"]["r"]) == \
            (parent["args"]["thread"], parent["args"]["r"])


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------

def _tiny_trace(path):
    with obs.capture(trace_path=str(path)):
        for i in range(6):
            with obs.span("solver.solve", sinkhorn_iters=360,
                          residual=1e-5 * (i + 1)):
                sum(range(200))
        tr = obs.tracer()
        tr.counter("sim/carbon_g", {"R0": 10.0 * (1 + 0)}, ts_us=0.0,
                   pid=obs.SIM_PID)
        tr.counter("sim/carbon_g", {"R0": 20.0}, ts_us=3.6e9,
                   pid=obs.SIM_PID)


def test_report_cli_smoke(tmp_path, capsys):
    from repro.obs import report
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _tiny_trace(a)
    _tiny_trace(b)
    assert report.main([str(a)]) == 0
    out = capsys.readouterr().out
    assert "solver.solve" in out and "p99_ms" in out
    assert "360" in out                       # sinkhorn iters column
    assert report.main([str(a), "--validate"]) == 0
    assert "schema OK" in capsys.readouterr().out
    assert report.main(["--diff", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "solver.solve" in out and "Δp99" in out


def test_report_rejects_bad_schema(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('[\n{"name": "x", "ph": "Q", "ts": 0},\n')
    from repro.obs import report
    assert report.main([str(bad), "--validate"]) == 1
