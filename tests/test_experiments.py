"""Declarative experiment API: ScenarioSpec grammar, ExperimentPlan JSON,
executor backend parity (serial == process == sharded, bit-identical
totals), arrival-time trace slicing, engine-state handoff, and sweep
failure handling."""
import copy
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import experiments, policy
from repro.sim import scenarios
from repro.sim.engine import EventSimulator
from repro.sim.trace import (borg_trace, pick_shard_boundaries,
                             slice_by_arrival)
from repro.spec import (ParamValueError, SpecSyntaxError, UnknownNameError,
                        UnknownParamError, split_specs)

CELL = "diurnal[days=0.1,jobs_per_day=20000.0,tolerance=0.5]"


# ---------------------------------------------------------------------------
# ScenarioSpec grammar
# ---------------------------------------------------------------------------

def test_scenario_spec_typed_params_and_round_trip():
    spec = experiments.parse_scenario(
        "diurnal[days=10.0,jobs_per_day=1e6,tolerance=0.5,seed=3]")
    assert spec.name == "diurnal"
    assert spec.params == {"days": 10.0, "jobs_per_day": 1e6,
                           "tolerance": 0.5, "seed": 3}
    assert isinstance(spec.params["seed"], int)
    assert isinstance(spec.params["jobs_per_day"], float)
    assert experiments.parse_scenario(str(spec)) == spec
    assert experiments.parse_scenario("nominal[]") == \
        experiments.parse_scenario("nominal")
    # Builder params come from the builder signature (trace, ewif_table...).
    spec = experiments.parse_scenario("burst-storm[trace=alibaba]")
    assert spec.params == {"trace": "alibaba"}


def test_scenario_spec_errors_have_did_you_mean():
    with pytest.raises(UnknownNameError, match="diurnal"):
        experiments.parse_scenario("diurnl")
    with pytest.raises(KeyError):            # UnknownNameError is a KeyError
        experiments.parse_scenario("no-such-regime")
    with pytest.raises(UnknownParamError, match="jobs_per_day"):
        experiments.parse_scenario("diurnal[jobs_per_da=1.0]")
    with pytest.raises(ParamValueError, match="float"):
        experiments.parse_scenario("diurnal[days=abc]")
    with pytest.raises(ParamValueError, match="int"):
        experiments.parse_scenario("diurnal[seed=1.5]")
    with pytest.raises(SpecSyntaxError):
        experiments.parse_scenario("diurnal[days=1")


def test_scenario_spec_split_and_cell_kwargs():
    spec = experiments.parse_scenario("diurnal[days=0.5,trace=alibaba]")
    cell = spec.cell_kwargs()
    assert cell["days"] == 0.5 and cell["seed"] == 0
    assert cell["jobs_per_day"] == 23000.0 and cell["window_s"] == 30.0
    assert spec.build_kwargs() == {"trace": "alibaba"}
    over = spec.with_params(seed=7)
    assert over.params["seed"] == 7 and over.params["days"] == 0.5
    kept = spec.with_defaults(days=9.0, seed=7)
    assert kept.params["days"] == 0.5 and kept.params["seed"] == 7


def _scenario_spec_strategy():
    def params_for(name):
        schema = experiments.scenario_schema(name)
        by_type = {
            float: st.floats(allow_nan=False, allow_infinity=False,
                             width=64),
            int: st.integers(-10**9, 10**9),
            bool: st.booleans(),
            str: st.text(alphabet="abcdefghijklmnopqrstuvwxyz0123456789_-",
                         min_size=1, max_size=12),
        }
        opts = {k: by_type[p.type] for k, p in schema.items()}
        return st.fixed_dictionaries({}, optional=opts).map(
            lambda d: experiments.ScenarioSpec(name, d))
    return st.sampled_from(scenarios.list_scenarios()).flatmap(params_for)


@settings(max_examples=100, deadline=None)
@given(spec=_scenario_spec_strategy())
def test_scenario_spec_format_parse_round_trip_property(spec):
    text = spec.format()
    back = experiments.parse_scenario(text)
    assert back == spec
    assert back.format() == text


# ---------------------------------------------------------------------------
# ExperimentPlan
# ---------------------------------------------------------------------------

def test_plan_cells_cross_product_and_json_round_trip(tmp_path):
    plan = experiments.ExperimentPlan.build(
        scenarios=["diurnal[days=0.05]", "drought-summer"],
        policies=["baseline", "waterwise[lam_h2o=0.7]"],
        seeds=[0, 1])
    cells = plan.cells()
    assert len(cells) == 8                   # 2 scenarios × 2 seeds × 2 pols
    # Scenario-major, then seed, then policy (the old sweep's row order).
    assert [  (c.scenario.name, c.seed, c.policy.name) for c in cells[:4]] == \
        [("diurnal", 0, "baseline"), ("diurnal", 0, "waterwise"),
         ("diurnal", 1, "baseline"), ("diurnal", 1, "waterwise")]
    assert cells[0].resolved_scenario().params["seed"] == 0
    assert cells[2].resolved_scenario().params["seed"] == 1

    back = experiments.ExperimentPlan.from_json(plan.to_json())
    assert back == plan
    path = tmp_path / "plan.json"
    plan.save(str(path))
    assert experiments.ExperimentPlan.load(str(path)) == plan
    with pytest.raises(ValueError, match="unknown ExperimentPlan keys"):
        experiments.ExperimentPlan.from_json('{"scenarios": [], "pols": []}')


def test_plan_validates_up_front():
    with pytest.raises(UnknownNameError):
        experiments.ExperimentPlan.build(["nominl"], ["baseline"])
    with pytest.raises(UnknownNameError):
        experiments.ExperimentPlan.build(["nominal"], ["baselin"])
    with pytest.raises(UnknownParamError):
        experiments.ExperimentPlan.build(["nominal[dayz=1.0]"], ["baseline"])


def test_executor_specs_share_the_grammar():
    ex = experiments.get_executor("sharded[shards=4,handoff_s=100.0]")
    assert (ex.shards, ex.handoff_s) == (4, 100.0)
    ex = experiments.get_executor("process", max_workers=3)
    assert ex.max_workers == 3
    with pytest.raises(UnknownNameError, match="sharded"):
        experiments.get_executor("sharted")
    with pytest.raises(UnknownParamError, match="shards"):
        experiments.get_executor("sharded[shard=2]")
    assert set(experiments.list_executors()) == \
        {"serial", "process", "sharded", "device"}


# ---------------------------------------------------------------------------
# Arrival-time slicing (the sharded executor's partition)
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 10_000), shards=st.integers(1, 6))
def test_slice_by_arrival_partitions_exactly(seed, shards):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(0, 200))
    jobs = borg_trace(days=0.05, seed=seed, tolerance=0.5)[:n]
    boundaries = pick_shard_boundaries(jobs, shards)
    assert len(boundaries) <= shards - 1
    assert boundaries == sorted(boundaries)
    slices = slice_by_arrival(jobs, boundaries)
    assert len(slices) == len(boundaries) + 1
    # Exact partition: no loss, no duplication.
    merged = [j.job_id for sl in slices for j in sl]
    assert sorted(merged) == sorted(j.job_id for j in jobs)
    assert len(merged) == len(jobs)
    # Arrival-contiguous: every job in slice k respects the boundaries, and
    # input order is preserved within each slice.
    for k, sl in enumerate(slices):
        lo = boundaries[k - 1] if k > 0 else -np.inf
        hi = boundaries[k] if k < len(boundaries) else np.inf
        for j in sl:
            assert lo <= j.submit_time_s < hi
        ids = [j.job_id for j in sl]
        in_order = [j.job_id for j in jobs if j.job_id in set(ids)]
        assert ids == in_order


# ---------------------------------------------------------------------------
# Engine-state handoff: chained slice runs == one uninterrupted run
# ---------------------------------------------------------------------------

def _record_sig(res):
    return [(r.job.job_id, r.region, r.start_s, r.finish_s, r.carbon_g,
             r.water_l) for r in res["records"]]


@pytest.mark.parametrize("spec", ["round-robin",
                                  "waterwise-forecast[warmup_hours=4]"])
def test_chained_handoff_matches_single_run_bitwise(spec):
    """Stateful schedulers shard exactly through the engine-state handoff:
    stopping/exporting at boundaries and resuming with the same scheduler
    object reproduces the single run's records bit-for-bit."""
    inst = scenarios.get_scenario("nominal").build(0.05, 0, 23000.0, 0.15)
    single = EventSimulator(inst.tele, inst.capacity).run(
        copy.deepcopy(inst.jobs), spec)

    jobs = copy.deepcopy(inst.jobs)
    boundaries = pick_shard_boundaries(jobs, 3)
    slices = slice_by_arrival(jobs, boundaries)
    sched = policy.build(spec, inst.tele)
    sim = EventSimulator(inst.tele, inst.capacity)
    state, merged = None, []
    for k, sl in enumerate(slices):
        stop = boundaries[k] if k < len(boundaries) else None
        res = sim.run(sl, sched, state=state, stop_at=stop,
                      export_state=stop is not None)
        state = res.get("state")
        merged += _record_sig(res)
    assert merged == _record_sig(single)


# ---------------------------------------------------------------------------
# Executor backend parity (acceptance: identical tidy rows)
# ---------------------------------------------------------------------------

# Timing-derived columns can never be bit-stable; merged utilization is
# recomposed from per-slice integrals (equal in value, float association
# differs — compared approximately below).
_NONDET_COLS = ("wall_s", "mean_solve_ms", "utilization")


def _assert_rows_match(a, b):
    assert set(a) - {"_result"} == set(b) - {"_result"}
    for key in a:
        if key in _NONDET_COLS or key.startswith("_"):
            continue
        assert a[key] == b[key], f"column {key!r}: {a[key]} != {b[key]}"
    assert a["utilization"] == pytest.approx(b["utilization"], rel=1e-9)


def test_serial_process_sharded_backends_produce_identical_rows():
    """Acceptance: the three executors are interchangeable — identical
    rows, carbon/water totals bit-identical, on a 2-shard diurnal cell for
    both a stateless policy (speculative parallel path) and a stateful
    one (chained handoff path)."""
    plan = experiments.ExperimentPlan.build(
        scenarios=[CELL], policies=["baseline", "waterwise[backend=flow]"])
    serial = plan.run(executor="serial")
    process = plan.run(executor="process[max_workers=2]")
    sharded = plan.run(executor="sharded[shards=2]")
    assert len(serial) == len(process) == len(sharded) == 2
    for s, p, sh in zip(serial, process, sharded):
        _assert_rows_match(s, p)
        _assert_rows_match(s, sh)
        assert s["carbon_kg"] == p["carbon_kg"] == sh["carbon_kg"]
        assert s["water_kl"] == p["water_kl"] == sh["water_kl"]
        assert s["violation_pct"] == p["violation_pct"] == sh["violation_pct"]
        assert not s["error"]


def test_sharded_rows_reparse_and_seed_axis():
    plan = experiments.ExperimentPlan.build(
        scenarios=["diurnal[days=0.05]"], policies=["baseline"],
        seeds=[0, 1])
    rows = plan.run(executor="sharded[shards=2]")
    assert [r["seed"] for r in rows] == [0, 1]
    assert rows[0]["carbon_kg"] != rows[1]["carbon_kg"]   # seeds differ
    for row in rows:
        sc = experiments.parse_scenario(row["scenario_spec"])
        assert sc.params["seed"] == row["seed"]
        assert policy.parse(row["spec"]).name == row["scheduler"]


# ---------------------------------------------------------------------------
# Failure handling (satellite: one crashed cell never aborts the sweep)
# ---------------------------------------------------------------------------

@pytest.fixture()
def crash_scenario():
    @scenarios.register("crash-test", "always-raising builder (tests only)")
    def _crash(days, seed, jobs_per_day, utilization, **kw):
        raise RuntimeError("builder exploded")
    yield "crash-test"
    scenarios._REGISTRY.pop("crash-test", None)


def test_failed_cell_records_error_row_and_others_finish(crash_scenario):
    plan = experiments.ExperimentPlan.build(
        scenarios=["crash-test", "diurnal[days=0.02]"],
        policies=["baseline"])
    rows = plan.run(executor="serial")
    assert len(rows) == 2
    bad, good = rows
    assert "builder exploded" in bad["error"]
    assert "carbon_kg" not in bad                    # metrics stay empty
    assert good["error"] == "" and good["jobs"] > 0


def test_sweep_raises_enriched_error_after_finishing_other_cells(
        crash_scenario):
    with pytest.raises(experiments.CellError) as ei:
        scenarios.sweep(["baseline"], ["crash-test", "diurnal"], days=0.02,
                        max_workers=1)
    err = ei.value
    assert "crash-test" in err.scenario and err.spec == "baseline"
    assert "builder exploded" in str(err)
    # Every other cell finished; all rows ride on the exception.
    assert len(err.rows) == 2
    good = [r for r in err.rows if not r.get("error")]
    assert len(good) == 1 and good[0]["scenario"] == "diurnal"


def test_process_executor_survives_worker_crash(crash_scenario):
    plan = experiments.ExperimentPlan.build(
        scenarios=["crash-test", "diurnal[days=0.02]"],
        policies=["baseline"])
    rows = plan.run(executor="process[max_workers=2]")
    assert "builder exploded" in rows[0]["error"]
    assert rows[1]["error"] == "" and rows[1]["jobs"] > 0


@pytest.mark.parametrize("cells,code", [
    ("crash-test,diurnal[days=0.02]", 1),
    ("diurnal[days=0.02]", None),
])
def test_sweep_cli_exits_nonzero_when_a_cell_failed(crash_scenario,
                                                    monkeypatch, cells, code):
    from repro.runtime import platform

    monkeypatch.syspath_prepend(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import run as bench_run

    monkeypatch.setattr(platform, "use_compile_cache", lambda: "")
    monkeypatch.setattr("sys.argv", [
        "run.py", "--sweep", "--scenarios", cells, "--schedulers",
        "baseline", "--executor", "serial"])
    if code is None:
        bench_run.main()
    else:
        with pytest.raises(SystemExit) as ei:
            bench_run.main()
        assert ei.value.code == code


# ---------------------------------------------------------------------------
# One process per chip: no pool worker takes the TPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,device", [
    ("baseline", False),
    ("waterwise", False),                           # backend=flow default
    ("waterwise[backend=fused]", True),
    ("waterwise[backend=jax]", True),
    ("waterwise-forecast", True),                   # Holt-Winters in JAX
    ("waterwise-forecast[forecaster=seasonal-naive,backend=flow]", False),
    ("waterwise-forecast[forecaster=learned,backend=flow]", True),
])
def test_needs_device_classifies_policies(spec, device):
    cell = experiments.ExperimentPlan.build([CELL], [spec]).cells()[0]
    assert experiments.needs_device(cell) is device


@pytest.mark.parametrize("kind,name,device", [
    ("solver", "flow", False), ("solver", "scipy", False),
    ("solver", "jax", True), ("solver", "fused", True),
    ("forecaster", "oracle", False), ("forecaster", "persistence", False),
    ("forecaster", "seasonal-naive", False),
    ("forecaster", "holtwinters", True), ("forecaster", "learned", True),
])
def test_registries_declare_device_work(kind, name, device):
    from repro import forecast
    from repro.core import solvers
    registry = solvers if kind == "solver" else forecast
    assert registry.on_device(name) is device


@pytest.fixture()
def tpu_host(monkeypatch):
    """The platform probe answers "tpu"; the backend stays the CPU."""
    from repro.runtime import platform
    monkeypatch.setattr(platform, "on_tpu", lambda: True)


def test_pool_executors_refuse_device_cells_on_a_tpu_host(tpu_host,
                                                         monkeypatch):
    from repro.experiments import executor

    plan = experiments.ExperimentPlan.build(
        [CELL], ["baseline", "waterwise[backend=fused]"])
    assert experiments.default_executor(plan.cells()) == "serial"
    with pytest.raises(RuntimeError, match="chip belongs to one process"):
        plan.run(executor="process[max_workers=2]")
    # Only stateless policies reach the sharded backend's pool, and none of
    # the registered ones needs the device: pretend one does.
    monkeypatch.setattr(executor, "needs_device", lambda cell: True)
    row, = experiments.ExperimentPlan.build([CELL], ["baseline"]).run(
        executor="sharded[shards=2]")
    assert "chip belongs to one process" in row["error"]


def test_host_only_cells_still_fan_out_on_a_tpu_host(tpu_host):
    plan = experiments.ExperimentPlan.build(
        [CELL], ["baseline", "waterwise[backend=flow]"])
    assert experiments.default_executor(plan.cells()) == "process"
    serial = plan.run(executor="serial")
    for s, p in zip(serial, plan.run(executor="process[max_workers=2]")):
        _assert_rows_match(s, p)


def _worker_platforms():
    import jax
    return os.environ["JAX_PLATFORMS"], jax.config.jax_platforms


def test_pool_workers_are_held_to_the_cpu(monkeypatch):
    from repro.experiments.executor import host_pool

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")     # inherited by the worker
    with host_pool(1) as pool:
        assert pool.submit(_worker_platforms).result(timeout=120) == \
            ("cpu", "cpu")


# ---------------------------------------------------------------------------
# Shard-merged forecast/deferral fields (satellite: job-weighted, never
# dropped when only some shards defer)
# ---------------------------------------------------------------------------

def test_merge_forecast_stats_is_job_weighted():
    merged = experiments.merge_forecast_stats([
        dict(forecast_mape=10.0, mean_defer_s=100.0, deferred_jobs=50,
             jobs=100, deferred_pct=50.0),
        dict(forecast_mape=20.0, mean_defer_s=300.0, deferred_jobs=0,
             jobs=300, deferred_pct=0.0),      # this shard never defers
    ])
    assert merged["jobs"] == 400 and merged["deferred_jobs"] == 50
    assert merged["forecast_mape"] == pytest.approx(
        (10.0 * 100 + 20.0 * 300) / 400)
    # mean_defer_s weights by *deferred* jobs: the non-deferring shard
    # contributes nothing instead of diluting the average.
    assert merged["mean_defer_s"] == pytest.approx(100.0)
    assert merged["deferred_pct"] == pytest.approx(12.5)


def test_merge_forecast_stats_absent_for_non_forecast_policies():
    assert experiments.merge_forecast_stats([None, None]) is None
    one = experiments.merge_forecast_stats(
        [None, dict(forecast_mape=5.0, mean_defer_s=60.0, deferred_jobs=2,
                    jobs=10, deferred_pct=20.0)])
    assert one is not None and one["deferred_jobs"] == 2


def test_sharded_forecast_cell_matches_serial_stats():
    """A forecast policy sharded (chained handoff) reports the same
    deferral telemetry as the serial run — the fields survive the merge."""
    plan = experiments.ExperimentPlan.build(
        scenarios=["diurnal[days=0.05,tolerance=3.0]"],
        policies=["waterwise-forecast[warmup_hours=4]"])
    serial = plan.run(executor="serial")[0]
    sharded = plan.run(executor="sharded[shards=2]")[0]
    assert serial["deferred_pct"] == sharded["deferred_pct"]
    assert serial["forecast_mape"] == sharded["forecast_mape"]
    assert serial["mean_defer_s"] == sharded["mean_defer_s"]


# ---------------------------------------------------------------------------
# Opt-in scale check (acceptance: >=200k-job cell, bit-identical totals;
# >=2.5x wall-clock at 4 shards on machines with >=4 CPUs)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not os.environ.get("REPRO_SHARD_PERF"),
                    reason="set REPRO_SHARD_PERF=1 to run the 200k-job "
                           "sharded parity + speedup check (minutes)")
def test_sharded_200k_cell_parity_and_speedup():
    import time
    plan = experiments.ExperimentPlan.build(
        scenarios=["diurnal[days=2.0,jobs_per_day=1.05e5,tolerance=0.5]"],
        policies=["water-greedy-opt"])
    t0 = time.perf_counter()
    serial = plan.run(executor="serial")[0]
    t_serial = time.perf_counter() - t0
    assert serial["jobs"] >= 200_000
    t0 = time.perf_counter()
    sharded = plan.run(executor="sharded[shards=4]")[0]
    t_sharded = time.perf_counter() - t0
    assert sharded["carbon_kg"] == serial["carbon_kg"]
    assert sharded["water_kl"] == serial["water_kl"]
    assert sharded["violation_pct"] == serial["violation_pct"]
    assert sharded["jobs"] == serial["jobs"]
    speedup = t_serial / t_sharded
    print(f"\n# sharded 200k cell: serial {t_serial:.1f}s, "
          f"4-shard {t_sharded:.1f}s, speedup {speedup:.2f}x "
          f"({os.cpu_count()} CPUs)")
    if (os.cpu_count() or 1) >= 4:
        assert speedup >= 2.5


def test_more_shards_than_arrivals_degrades_gracefully():
    """Degenerate shard counts yield fewer boundaries instead of crashing
    (and the sharded executor still produces the exact row)."""
    jobs = borg_trace(days=0.01, seed=0, tolerance=0.5)[:4]
    bounds = pick_shard_boundaries(jobs, 10)
    assert len(bounds) <= 3
    plan = experiments.ExperimentPlan.build(
        scenarios=["diurnal[days=0.01]"], policies=["baseline"])
    rows = plan.run(executor="sharded[shards=64,max_workers=1]")
    assert rows[0]["error"] == "" and rows[0]["jobs"] > 0


def test_savings_group_by_scenario_spec_not_name():
    """Two param-variants of one scenario each get their own baseline."""
    small = "diurnal[days=0.03,jobs_per_day=10000.0]"
    big = "diurnal[days=0.03,jobs_per_day=40000.0]"
    rows = experiments.ExperimentPlan.build(
        scenarios=[small, big],
        policies=["baseline", "least-load"]).run(executor="serial")
    by = {(r["scenario_spec"], r["scheduler"]): r for r in rows}
    for spec in (small, big):
        base = by[(spec, "baseline")]
        other = by[(spec, "least-load")]
        assert base["carbon_savings_pct"] == 0.0
        expected = 100.0 * (base["carbon_kg"] - other["carbon_kg"]) \
            / base["carbon_kg"]
        assert other["carbon_savings_pct"] == pytest.approx(expected)


def test_split_specs_reexported_for_scenario_lists():
    assert split_specs("a[x=1,y=2], b ,c[z=3]") == \
        ["a[x=1,y=2]", "b", "c[z=3]"]


# ---------------------------------------------------------------------------
# Multi-seed confidence intervals (ROADMAP: rolling multi-seed studies)
# ---------------------------------------------------------------------------

def test_aggregate_seeds_ci_math_pinned():
    """CI math on a fixed 3-seed cell: mean ± t_{0.975,2}·s/√3 with the
    sample std (ddof=1), exactly."""
    rows = [dict(scenario="nominal", scheduler="baseline", spec="baseline",
                 scenario_spec=f"nominal[days=0.2,seed={s}]", seed=s,
                 error="", carbon_kg=v, jobs=100)
            for s, v in zip((0, 1, 2), (10.0, 12.0, 14.0))]
    agg = experiments.aggregate_seeds(rows)
    assert len(agg) == 1
    a = agg[0]
    assert a["n_seeds"] == 3 and a["seed"] == "0,1,2"
    # The aggregated row's spec columns are the seed-stripped group
    # identity, not the first replicate's seed-bearing spec.
    assert a["scenario_spec"] == "nominal[days=0.2]"
    assert a["carbon_kg"] == pytest.approx(12.0)
    # sample std of (10, 12, 14) is 2.0; t_{0.975, df=2} = 4.302652729911275
    assert a["carbon_kg_ci95"] == pytest.approx(
        4.302652729911275 * 2.0 / np.sqrt(3.0), rel=1e-12)
    assert experiments.t95(2) == pytest.approx(4.302652729911275)
    assert experiments.t95(1000) == pytest.approx(1.959963984540054)
    # Zero-variance metrics aggregate to ±0.00.
    assert a["jobs"] == pytest.approx(100.0)
    assert a["jobs_ci95"] == pytest.approx(0.0)


def test_to_table_emits_ci_columns_for_multi_seed_rows():
    rows = [dict(scenario="nominal", scheduler="baseline", spec="baseline",
                 scenario_spec=f"nominal[days=0.2,seed={s}]", seed=s,
                 error="", carbon_kg=v)
            for s, v in zip((0, 1, 2), (10.0, 12.0, 14.0))]
    table = experiments.to_table(rows, ("scenario", "scheduler",
                                        "carbon_kg"))
    assert "12.00±4.97" in table
    assert table.count("baseline") == 1          # collapsed to one line
    # Single-seed rows render unchanged, and ci=False disables aggregation.
    assert "±" not in experiments.to_table(rows[:1],
                                           ("scenario", "carbon_kg"))
    assert "±" not in experiments.to_table(rows, ("scenario", "carbon_kg"),
                                           ci=False)


def test_seed_group_key_strips_seed_and_forecast_seed():
    a = dict(scenario_spec="nominal[days=0.2,seed=0]",
             spec="waterwise-forecast[forecast_bias=1.3,forecast_seed=0]")
    b = dict(scenario_spec="nominal[days=0.2,seed=1]",
             spec="waterwise-forecast[forecast_bias=1.3,forecast_seed=1]")
    assert experiments.seed_group_key(a) == experiments.seed_group_key(b)
    c = dict(scenario_spec="nominal[days=0.5,seed=1]", spec="waterwise")
    assert experiments.seed_group_key(a) != experiments.seed_group_key(c)


def test_multi_seed_plan_end_to_end_ci():
    """A real 3-seed plan: one aggregated row per cell, CI columns on the
    metrics, error-free."""
    plan = experiments.ExperimentPlan.build(
        scenarios=["nominal[days=0.02]"], policies=["baseline"],
        seeds=[0, 1, 2])
    rows = plan.run(executor="serial")
    assert len(rows) == 3
    assert sorted(r["seed"] for r in rows) == [0, 1, 2]
    agg = experiments.aggregate_seeds(rows)
    assert len(agg) == 1
    assert agg[0]["n_seeds"] == 3
    assert agg[0]["carbon_kg_ci95"] >= 0.0
    assert "±" in experiments.to_table(rows)


def test_aggregate_seeds_keeps_error_rows_unaggregated():
    ok = [dict(scenario="nominal", scheduler="baseline", spec="baseline",
               scenario_spec=f"nominal[seed={s}]", seed=s, error="",
               carbon_kg=1.0 * s) for s in (0, 1)]
    bad = dict(scenario="nominal", scheduler="waterwise", spec="waterwise",
               scenario_spec="nominal[seed=0]", seed=0,
               error="RuntimeError: boom")
    agg = experiments.aggregate_seeds(ok + [bad])
    assert len(agg) == 2
    assert agg[0]["n_seeds"] == 2
    assert agg[1]["error"].startswith("RuntimeError")
