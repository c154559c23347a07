"""Run every paper benchmark (quick mode) + the roofline report.

  PYTHONPATH=src python -m benchmarks.run            # quick (~minutes)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale traces
  PYTHONPATH=src python -m benchmarks.run --only fig5,table2

Scenario sweep (event-driven engine, schedulers × scenarios cross product;
``--schedulers`` takes policy-spec strings and ``--scenarios`` scenario-spec
strings, bracketed params included):

  PYTHONPATH=src python -m benchmarks.run --sweep            # quick
  PYTHONPATH=src python -m benchmarks.run --sweep --full     # 100k jobs/10d
  PYTHONPATH=src python -m benchmarks.run --sweep \\
      --schedulers 'baseline,waterwise[lam_h2o=0.7,backend=jax]' \\
      --scenarios 'diurnal[jobs_per_day=1e5],drought-summer'
  PYTHONPATH=src python -m benchmarks.run --sweep \\
      --scenarios 'workflow-diurnal,workflow-burst' \\
      --schedulers 'waterwise,waterwise-embodied[lam_embodied=0.35]'
      # DAG traces: precedence release + critical-path deadlines +
      # the embodied-carbon accounting column

Executor backends (identical rows, different scaling): ``--executor
serial``, ``--executor process`` (one worker per cell, the default), or
``--executor 'sharded[shards=4]'`` / ``--shards 4`` (split each cell's
trace by arrival time across workers — the 1M+-job single-cell path).
Worker processes never touch the TPU: on a TPU host the default becomes
``serial`` when a cell needs the device, and ``process``/``sharded``
refuse such cells. The sweep exits non-zero when any cell failed.

Experiment plans are JSON artifacts: ``--save-plan plan.json`` writes the
sweep's (scenarios × policies × seeds) grid without running it;
``--plan plan.json`` runs a saved plan.

Forecast-quality benchmark (every registered forecaster + the oracle,
walk-forward MAPE / pinball / coverage on one telemetry signal; asserts the
oracle lower-bounds every model):

  PYTHONPATH=src python -m benchmarks.run --forecast-bench
  PYTHONPATH=src python -m benchmarks.run --forecast-bench \\
      --days 10 --train-steps 600 --signal wue

Streaming-service benchmark (the persisted BENCH_8 harness — batch/stream
parity, Sinkhorn warm-start carry, receding-horizon re-planning deltas, and
a Poisson-burst storm through the bounded admission loop):

  PYTHONPATH=src python -m benchmarks.run --serve
  PYTHONPATH=src python -m benchmarks.serve_bench --quick \\
      --check BENCH_8.json                               # the CI gate

Workflow (DAG) benchmark (the persisted BENCH_9 harness — precedence
release, critical-path deadlines, DAG batch/stream bit parity, and the
embodied-carbon trade-off curve):

  PYTHONPATH=src python -m benchmarks.workflow_bench
  PYTHONPATH=src python -m benchmarks.workflow_bench --quick \\
      --check BENCH_9.json                               # the CI gate

Registries (names, accepted params, descriptions):

  PYTHONPATH=src python -m benchmarks.run --list-schedulers  [--markdown]
  PYTHONPATH=src python -m benchmarks.run --list-scenarios   [--markdown]
  PYTHONPATH=src python -m benchmarks.run --list-forecasters [--markdown]
"""
from __future__ import annotations

import argparse
import os
import time


def list_schedulers(markdown: bool) -> None:
    from repro import policy
    print(policy.describe(markdown=markdown))


def list_scenarios(markdown: bool) -> None:
    from repro import experiments
    print(experiments.describe_scenarios(markdown=markdown))


def list_forecasters(markdown: bool) -> None:
    from repro import forecast
    print(forecast.describe_forecasters(markdown=markdown))


def build_plan(args):
    from repro import experiments, policy
    from repro.spec import split_specs

    full = args.full
    days = args.days if args.days is not None else (10.0 if full else 0.2)
    jobs_per_day = (args.jobs_per_day if args.jobs_per_day is not None
                    else (10000.0 if full else 23000.0))
    if args.trace_csv:
        from repro.sim import scenarios as scen_registry
        scen_registry.register_csv_scenario("csv-trace", args.trace_csv)
    names = (split_specs(args.scenarios) if args.scenarios
             else None)
    if names is None:
        from repro.sim import scenarios as scen_registry
        names = scen_registry.list_scenarios()
    params = dict(days=days, seed=args.seed, jobs_per_day=jobs_per_day)
    if args.tolerance is not None:
        params["tolerance"] = args.tolerance
    scenario_specs = [
        experiments.parse_scenario(n).with_defaults(**params) for n in names]
    policies = [policy.as_spec(s) for s in split_specs(args.schedulers)]
    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds else None)
    return experiments.ExperimentPlan(tuple(scenario_specs), tuple(policies),
                                      tuple(seeds) if seeds else (None,))


def print_metrics_table(snap) -> None:
    """Per-stage latency table from an obs metrics snapshot."""
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    reg.merge(snap)
    if reg.hists:
        print("\n# per-stage latency (obs):")
        print(f"# {'stage':24s} {'count':>7s} {'p50 ms':>10s} "
              f"{'p95 ms':>10s} {'p99 ms':>10s}")
        for name in sorted(reg.hists):
            h = reg.hists[name]
            print(f"# {name:24s} {h.count:7d} {h.quantile(50)*1e3:10.3f} "
                  f"{h.quantile(95)*1e3:10.3f} {h.quantile(99)*1e3:10.3f}")
    warn = {k: v for k, v in snap.get("counters", {}).items()
            if k.startswith("warn/")}
    for k, v in sorted(warn.items()):
        print(f"# {k}: {v:.0f}")


def run_sweep(args) -> int:
    """Run the sweep; returns the number of failed cells."""
    from repro import experiments

    if args.plan:
        plan = experiments.ExperimentPlan.load(args.plan)
    else:
        plan = build_plan(args)
    if args.save_plan:
        plan.save(args.save_plan)
        print(f"# plan ({len(plan.cells())} cells) -> {args.save_plan}")
        return 0
    executor = args.executor or experiments.default_executor(plan.cells())
    options = {}
    if args.shards is not None:
        executor = executor if executor.startswith("sharded") else "sharded"
        options["shards"] = args.shards
    if args.workers is not None:
        options["max_workers"] = args.workers
    out = os.path.join(os.path.dirname(__file__), "out")
    os.makedirs(out, exist_ok=True)
    trace_path = None
    if args.trace is not None:
        trace_path = args.trace or os.path.join(out, "run.trace.jsonl")
        if executor != "serial":
            # Trace events are per-process: pool workers would be dark.
            print(f"# --trace forces --executor serial (was [{executor}])")
            executor, options = "serial", {}
    collect = trace_path is not None or args.metrics
    t0 = time.time()
    if collect:
        import repro.obs as obs
        with obs.capture(trace_path=trace_path) as reg:
            rows = plan.run(executor=executor, strict=False, **options)
            snap = reg.snapshot()
    else:
        rows = plan.run(executor=executor, strict=False, **options)
    print(experiments.to_table(rows))
    csv = os.path.join(out, "scenario_sweep.csv")
    experiments.to_csv(rows, csv)
    failed = [r for r in rows if r.get("error")]
    total = sum(r.get("jobs", 0) for r in rows)
    print(f"\n# sweep: {len(rows)} cells ({len(failed)} failed), "
          f"{total} job-placements, {time.time() - t0:.1f}s wall "
          f"[{executor}] -> {csv}")
    for r in failed:
        print(f"# FAILED {r['scenario_spec']} × {r['spec']}: {r['error']}")
    if collect:
        print_metrics_table(snap)
    if trace_path is not None:
        print(f"# trace -> {trace_path} (load in https://ui.perfetto.dev "
              f"or: PYTHONPATH=src python -m repro.obs.report {trace_path})")
    return len(failed)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--sweep", action="store_true",
                    help="run the scenario sweep instead of the paper figures")
    ap.add_argument("--scenarios", default="",
                    help="comma-separated scenario specs, e.g. "
                         "'diurnal[jobs_per_day=1e5],drought-summer' or the "
                         "DAG cells 'workflow-diurnal,workflow-burst' "
                         "(default: all registered scenarios; see "
                         "--list-scenarios)")
    ap.add_argument("--schedulers",
                    default="baseline,least-load,ecovisor,waterwise",
                    help="comma-separated policy specs, e.g. "
                         "'baseline,waterwise[lam_h2o=0.7,backend=jax]'")
    ap.add_argument("--executor", default=None,
                    help="executor spec: serial | process[max_workers=N] | "
                         "sharded[shards=N,max_workers=N,handoff_s=S] | "
                         "device[devices=N,max_cells=N] (default: process; "
                         "serial on a TPU host when a cell needs the "
                         "device)")
    ap.add_argument("--shards", type=int, default=None,
                    help="shortcut: run with the sharded executor at N "
                         "shards per cell")
    ap.add_argument("--seeds", default="",
                    help="comma-separated seed axis for the plan "
                         "(multi-seed replication), e.g. '0,1,2'")
    ap.add_argument("--plan", default="",
                    help="run a saved ExperimentPlan JSON instead of "
                         "building one from the flags")
    ap.add_argument("--save-plan", default="",
                    help="write the plan JSON and exit without running")
    ap.add_argument("--list-schedulers", action="store_true",
                    help="print the policy registry (params, descriptions) "
                         "and exit")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print the scenario registry and exit")
    ap.add_argument("--list-forecasters", action="store_true",
                    help="print the forecaster registry and exit")
    ap.add_argument("--forecast-bench", action="store_true",
                    help="run the forecast-quality benchmark (walk-forward "
                         "MAPE/pinball/coverage per registered forecaster)")
    ap.add_argument("--serve", action="store_true",
                    help="run the streaming-service bench (batch parity, "
                         "Sinkhorn warm-start, receding-horizon re-planning, "
                         "Poisson-burst storm; `python -m "
                         "benchmarks.serve_bench` for --out/--check/--quick)")
    ap.add_argument("--signal", default="ci",
                    help="with --forecast-bench: telemetry signal to "
                         "forecast (ci / ewif / wue / water_intensity)")
    ap.add_argument("--train-steps", type=int, default=300,
                    help="with --forecast-bench: learned-forecaster "
                         "training steps per refit")
    ap.add_argument("--refit-every", type=int, default=4,
                    help="with --forecast-bench: walk-forward full-refit "
                         "cadence in origins (updates in between)")
    ap.add_argument("--warmup", type=int, default=None,
                    help="with --forecast-bench: first origin (hours of "
                         "history; default auto-sizes to the series)")
    ap.add_argument("--markdown", action="store_true",
                    help="with --list-schedulers/--list-scenarios: emit the "
                         "markdown table embedded in README.md")
    ap.add_argument("--days", type=float, default=None)
    ap.add_argument("--jobs-per-day", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--tolerance", type=float, default=None,
                    help="delay-tolerance override (TOL fraction of exec "
                         "time; the temporal-shifting slack dimension)")
    ap.add_argument("--trace", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="with --sweep: stream a Chrome-trace JSONL of the "
                         "run (default benchmarks/out/run.trace.jsonl; load "
                         "in Perfetto or render with `python -m "
                         "repro.obs.report`); forces the serial executor")
    ap.add_argument("--metrics", action="store_true",
                    help="with --sweep: collect repro.obs metrics and print "
                         "the per-stage p50/p95/p99 latency table after the "
                         "run")
    ap.add_argument("--trace-csv", default="",
                    help="register a real-trace CSV as scenario 'csv-trace' "
                         "(canonical columns: job_id,submit_s,duration_s,"
                         "energy_kwh,home_region)")
    args = ap.parse_args()

    if args.list_schedulers:
        list_schedulers(args.markdown)
        return
    if args.list_scenarios:
        list_scenarios(args.markdown)
        return
    if args.list_forecasters:
        list_forecasters(args.markdown)
        return
    from repro.runtime.platform import use_compile_cache
    use_compile_cache()
    if args.serve:
        from benchmarks import serve_bench
        raise SystemExit(serve_bench.main([]))
    if args.forecast_bench:
        sweep_flags = dict(sweep=args.sweep, scenarios=args.scenarios != "",
                           schedulers=args.schedulers
                           != ap.get_default("schedulers"),
                           executor=args.executor is not None,
                           shards=args.shards is not None,
                           seeds=args.seeds != "", plan=args.plan != "",
                           save_plan=args.save_plan != "",
                           workers=args.workers is not None,
                           tolerance=args.tolerance is not None,
                           trace_csv=args.trace_csv != "",
                           trace=args.trace is not None,
                           metrics=args.metrics,
                           jobs_per_day=args.jobs_per_day is not None)
        if any(sweep_flags.values()):
            ap.error("--" + ", --".join(k.replace("_", "-")
                                        for k, v in sweep_flags.items() if v)
                     + " do not apply with --forecast-bench")
        from benchmarks import forecast_bench
        forecast_bench.main(args)
        return
    bench_only = dict(signal=args.signal != "ci",
                      train_steps=args.train_steps
                      != ap.get_default("train_steps"),
                      refit_every=args.refit_every
                      != ap.get_default("refit_every"),
                      warmup=args.warmup is not None)
    if any(bench_only.values()):
        ap.error("--" + ", --".join(k.replace("_", "-")
                                    for k, v in bench_only.items() if v)
                 + " only apply with --forecast-bench")
    if args.sweep or args.plan:
        if args.only:
            ap.error("--only does not apply with --sweep "
                     "(use --scenarios/--schedulers to filter)")
        if run_sweep(args):
            raise SystemExit(1)
        return
    sweep_only = dict(scenarios=args.scenarios != "", days=args.days is not None,
                      jobs_per_day=args.jobs_per_day is not None,
                      seed=args.seed != 0, workers=args.workers is not None,
                      tolerance=args.tolerance is not None,
                      trace_csv=args.trace_csv != "",
                      trace=args.trace is not None,
                      metrics=args.metrics,
                      shards=args.shards is not None,
                      seeds=args.seeds != "",
                      save_plan=args.save_plan != "",
                      executor=args.executor is not None,
                      schedulers=args.schedulers
                      != ap.get_default("schedulers"))
    if any(sweep_only.values()):
        ap.error("--" + ", --".join(k.replace("_", "-")
                                    for k, v in sweep_only.items() if v)
                 + " only apply with --sweep")

    from benchmarks import figures
    from benchmarks.common import FULL_DAYS, QUICK_DAYS
    days = FULL_DAYS if args.full else QUICK_DAYS
    only = set(args.only.split(",")) if args.only else None

    t0 = time.time()
    for name, fn in figures.ALL.items():
        if only and name not in only:
            continue
        t1 = time.time()
        fn(days=days)
        print(f"# {name} done in {time.time() - t1:.1f}s\n", flush=True)

    if not only or "roofline" in (only or set()):
        try:
            from benchmarks import roofline
            print(roofline.table(multi_pod=False))
            print()
            print(roofline.summary())
        except Exception as e:  # dry-run results may not exist yet
            print(f"# roofline report unavailable: {e}")
    print(f"# all benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
