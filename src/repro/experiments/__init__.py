"""Declarative experiment API: scenario specs, plans, sharded execution.

The experiment-layer counterpart of ``repro.policy``: *what to run* is
data, not kwargs. A ``ScenarioSpec`` names a registered scenario with
typed, validated cell parameters (``"diurnal[days=10,jobs_per_day=1e6]"``);
an ``ExperimentPlan`` is the (scenarios × policies × seeds) grid, JSON-
serializable; ONE ``Executor`` abstraction runs a plan's cells on three
interchangeable backends — ``serial``, ``process`` (one worker per cell),
and ``sharded`` (one cell split by arrival time across workers with
engine-state handoff and boundary stitching). All backends produce
identical tidy rows; carbon/water/violation totals are bit-identical to
the serial run by construction.

Typical use::

    from repro import experiments

    plan = experiments.ExperimentPlan.build(
        scenarios=["diurnal[days=10,jobs_per_day=1e5]", "drought-summer"],
        policies=["baseline", "waterwise[lam_h2o=0.7]"],
        seeds=[0, 1, 2])
    rows = plan.run(executor="sharded[shards=4]")
    print(experiments.to_table(rows))
    plan.save("plan.json")                 # reviewable, re-runnable artifact

Everything a spec cannot express (an unknown scenario, a typo'd or
ill-typed param) fails fast with a did-you-mean message, before any cell
runs. The legacy ``repro.sim.scenarios.run_cell`` / ``sweep`` surface
survives as thin shims over this package.
"""
from repro.experiments.executor import (Executor, ProcessExecutor,
                                        SerialExecutor, ShardedExecutor,
                                        default_executor, describe_executors,
                                        executor_schema, get_executor,
                                        list_executors, needs_device)
from repro.experiments.plan import (CSV_COLS, TABLE_COLS, Cell,
                                    ExperimentPlan, aggregate_seeds,
                                    attach_savings, seed_group_key, t95,
                                    to_csv, to_table)
from repro.experiments.runner import CellError, run_cell
from repro.experiments.scenario import (CELL_PARAMS, ScenarioSpec,
                                        as_scenario_spec, build_instance,
                                        describe_scenarios,
                                        make_scenario_spec, parse_scenario,
                                        scenario_schema)
from repro.experiments.shard import (auto_handoff_s, merge_forecast_stats,
                                     run_sharded_cell, states_match)

__all__ = [
    # scenario specs
    "ScenarioSpec", "parse_scenario", "as_scenario_spec",
    "make_scenario_spec", "scenario_schema", "build_instance",
    "describe_scenarios", "CELL_PARAMS",
    # plans
    "ExperimentPlan", "Cell", "attach_savings", "TABLE_COLS", "CSV_COLS",
    "to_table", "to_csv", "aggregate_seeds", "seed_group_key", "t95",
    # running
    "run_cell", "CellError",
    # executors
    "Executor", "SerialExecutor", "ProcessExecutor", "ShardedExecutor",
    "get_executor", "list_executors", "executor_schema",
    "describe_executors", "default_executor", "needs_device",
    # sharding
    "run_sharded_cell", "auto_handoff_s", "merge_forecast_stats",
    "states_match",
]
