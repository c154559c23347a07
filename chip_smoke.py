#!/usr/bin/env python3
"""Bring the served scheduling round up on a TPU, end to end, and check it.

  python chip_smoke.py              # one chip: phases A, B and C
  python chip_smoke.py --chips 4    # four chips: the device executor only

Every phase runs in this one process and goes through the normal path:
policy spec → ``PolicyPipeline`` → fused device round → ``EventSimulator``
stepper under ``serve.DecisionLoop``.

  A  ``waterwise[backend=fused,record_windows=true]``
     the assignment program with the Pallas Sinkhorn kernel;
  B  ``waterwise-forecast[backend=fused,record_windows=true]``
     the cold temporal program (pricing + Eq-11 mask + Pallas Sinkhorn);
  C  ``waterwise-forecast[backend=fused,warm=true]``
     the warm-started adaptive temporal program (XLA loop), the live-serving
     configuration (recording windows disables the carry, hence its own run).

Traffic: ``PoissonBurstArrivals`` at 10⁶ jobs/day, ``burst=1.0``,
``tolerance=4.0``, 300 s rounds, the fleet sized by
``scale_capacity_for_utilization`` to 0.6 of the served window's load. The
stream opens with a 30-minute burst train at five times the base rate; the
served window starts where it ends (t = 1800 s), so each round carries the
base rate's ~3,700 arrivals (bucket 4096).

Checks, per phase: nothing shed; every placement within the free capacity
the engine offered and on an arc the Eq-11 mask allows; the Sinkhorn
implementation that ran, read from the ``round.sinkhorn/*`` counters
(the compiled Pallas kernel in A and B); at least one round of ``MIN_ROWS``
job rows. A and B also re-solve every recorded round with the exact ``flow``
solver and require the served objective within 2 % of it, and the Sinkhorn
plan's own fractional objective within ``PLAN_GAP_LIMIT``; a cost-blind
plan on the largest round must miss that limit, so the check can fail.
Deadline misses and the host's SSP repairs are printed.

``--chips 4`` runs only the multi-chip path: an 8-cell plan of
``waterwise[backend=fused]`` cells at the same rate and sizes through
``device[devices=4]`` and through ``serial``, requiring identical placements.

Any failure raises and exits non-zero. Without a TPU the script exits
non-zero before running a phase. The last line of standard output is one
JSON object naming the device; everything else comes before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro import experiments, policy  # noqa: E402
from repro.core import problem, telemetry  # noqa: E402
from repro.obs import counter_value  # noqa: E402
from repro.serve import DecisionLoop, PoissonBurstArrivals, ServeConfig  # noqa: E402
from repro.sim.engine import EventSimulator, SimConfig  # noqa: E402
from repro.sim.trace import scale_capacity_for_utilization  # noqa: E402

GAP_LIMIT = 0.02        # the integrality gap the solver tests pin
# The Sinkhorn plan's own fractional objective, before the host rounds,
# repairs and polishes it, must lie this close to the flow optimum. The
# served objective cannot show a broken kernel: the host's rounding and
# polish land within GAP_LIMIT from a cost-blind plan too. A cost-blind
# plan's fractional objective lands tens of percent off, and each run
# checks that on its largest round.
PLAN_GAP_LIMIT = 0.05
MIN_ROWS = 2048         # job rows the largest round of each phase reaches
SINKHORN_MODES = ("pallas", "pallas_interpret", "xla")

PHASES = {
    "A": "waterwise[backend=fused,record_windows=true]",
    "B": "waterwise-forecast[backend=fused,record_windows=true]",
    "C": "waterwise-forecast[backend=fused,warm=true]",
}


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def _require(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Traffic:
    """The served stream and fleet of one phase."""
    jobs_per_day: float = 1e6
    burst: float = 1.0
    tolerance: float = 4.0
    utilization: float = 0.6
    window_s: float = 300.0
    start_s: float = 1800.0     # end of the stream's first burst train
    rounds: int = 3
    seed: int = 0

    @property
    def end_s(self) -> float:
        return self.start_s + self.rounds * self.window_s

    def source(self, num_regions: int) -> PoissonBurstArrivals:
        return PoissonBurstArrivals(
            self.jobs_per_day / 86400.0, seed=self.seed,
            num_regions=num_regions, tolerance=self.tolerance,
            burst=self.burst, horizon_s=self.end_s)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    compilation-cache hits, summed from ``jax.monitoring`` events while the
    context is open."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in self.DURATIONS:
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileClock":
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> bool:
        import jax.monitoring as monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)
        return False


class AuditedScheduler:
    """Delegating scheduler that audits each decision before the engine
    dispatches it: placements per region within the free capacity the
    engine offered, and every placement on an arc the Eq-11 mask allows.
    Records the job rows of every solve."""

    def __init__(self, inner, tele: telemetry.Telemetry):
        self.inner = inner
        self.tele = tele
        self.rows: list = []
        self.over_capacity = 0
        self.masked = 0

    def schedule(self, jobs, now_s, capacity):
        dec = self.inner.schedule(jobs, now_s, capacity)
        if dec.solver is not None:
            self.rows.append(int(dec.solver.assign.shape[0]))
        if dec.scheduled:
            servers = np.array([j.servers for j in dec.scheduled], float)
            used = np.bincount(dec.assign, weights=servers,
                               minlength=len(capacity))
            self.over_capacity += int((used > capacity).sum())
            inst = problem.build(dec.scheduled, self.tele, now_s, capacity,
                                 self.inner.server)
            self.masked += int((~inst.allowed[np.arange(len(dec.assign)),
                                              dec.assign]).sum())
        return dec

    def __getattr__(self, name):
        return getattr(self.__dict__["inner"], name)


def _sinkhorn_counts() -> dict:
    return {m: counter_value(f"round.sinkhorn/{m}") for m in SINKHORN_MODES}


def _gap(objective: float, optimum: float) -> float:
    return (objective - optimum) / max(abs(optimum), 1e-9)


def flow_gaps(sched) -> dict:
    """Re-solve every recorded round with the exact ``flow`` solver, check
    the served assignment is feasible on that instance, and return, per
    round, the relative gap to the optimum of the served objective (after
    the host's rounding, repair and polish) and of the Sinkhorn plan's own
    fractional objective (before them).

    ``control`` holds a cost-blind plan, uniform over each job's allowed
    arcs, on the largest recorded round: its fractional gap, which the plan
    check must be able to see, and its served gap through the same host
    rounding, repair and polish."""
    from repro.core.solvers import jax_solver
    served, plan = [], []
    largest = None
    for w, ref in zip(sched.recorded, sched.replay_recorded(backend="flow")):
        assign = w["assign"]
        _require(ref.feasible, "flow found a recorded round infeasible")
        _require(bool((assign >= 0).all()), "a recorded round left a job "
                 "unassigned")
        used = np.bincount(assign, minlength=len(w["capacity"]))
        _require(bool((used <= w["capacity"]).all()),
                 "a recorded round exceeds a column's capacity")
        if not w["soften"]:
            _require(bool(w["allowed"][np.arange(len(assign)), assign].all()),
                     "a recorded round uses a masked arc")
        served.append(_gap(w["objective"], ref.objective))
        plan.append(_gap(w["plan_objective"], ref.objective))
        if not w["soften"] and (largest is None
                                or len(assign) > len(largest[0]["assign"])):
            largest = (w, ref)
    _require(largest is not None, "no hard round to run the control on")
    w, ref = largest
    mask = w["allowed"].astype(bool)
    blind = mask / mask.sum(axis=1, keepdims=True)
    scale = max(float(np.abs(w["cost"][mask]).max()), 1e-9)
    cn = np.where(mask, w["cost"] / scale, jax_solver.BIG)
    res = jax_solver._finalize(blind, cn, w["cost"], mask,
                               w["capacity"].astype(np.int64), False, None,
                               None)
    control = dict(rows=len(w["assign"]),
                   plan_gap=_gap(res.plan_objective, ref.objective),
                   flow_gap=_gap(res.objective, ref.objective))
    return dict(served=served, plan=plan, control=control)


def run_phase(name: str, spec: str, traffic: Traffic, *, kernel: str,
              forbid: tuple, min_rows: int, reference: bool,
              clock: CompileClock) -> dict:
    """Serve ``traffic`` with the policy ``spec`` and check the result
    (module docstring). The Sinkhorn must have run as ``kernel`` and never
    as a mode in ``forbid``. Returns the phase's numbers."""
    t0 = time.perf_counter()
    tele = telemetry.generate(days=1, seed=traffic.seed)
    R = tele.num_regions
    window = [j for j in traffic.source(R).poll(traffic.end_s)
              if j.submit_time_s >= traffic.start_s]
    cap = scale_capacity_for_utilization(
        window, traffic.rounds * traffic.window_s / 86400.0, R,
        traffic.utilization)
    src = traffic.source(R)
    src.poll(traffic.start_s)           # the served window opens here
    sched = AuditedScheduler(policy.build(spec, tele), tele)
    loop = DecisionLoop(
        EventSimulator(tele, cap, SimConfig(window_s=traffic.window_s)),
        sched, src, ServeConfig(round_s=traffic.window_s))
    setup_s = time.perf_counter() - t0

    before = _sinkhorn_counts()
    repairs0 = counter_value("solver.ssp_repair")
    compile0, hits0 = clock.seconds, clock.cache_hits
    t1 = time.perf_counter()
    rep = loop.run(traffic.end_s)
    serve_s = time.perf_counter() - t1
    ran = {m: _sinkhorn_counts()[m] - before[m] for m in SINKHORN_MODES}
    out = dict(
        phase=name, spec=spec, jobs=len(window), capacity=int(cap.sum()),
        setup_s=setup_s, serve_s=serve_s,
        compile_s=clock.seconds - compile0,
        cache_hits=clock.cache_hits - hits0, solves=len(sched.rows),
        max_rows=max(sched.rows, default=0), sinkhorn=ran,
        ssp_repairs=counter_value("solver.ssp_repair") - repairs0,
        placed=rep.placed, shed=rep.shed,
        deadline_misses=rep.deadline_misses, carbon_kg=rep.carbon_kg,
        water_kl=rep.water_kl, p99_round_ms=rep.p99_round_ms)
    from repro.core.solvers.jax_solver import bucket_for
    out["max_bucket"] = bucket_for(out["max_rows"] + 1)

    _require(rep.shed == 0, f"phase {name}: {rep.shed} jobs shed")
    _require(rep.placed == rep.admitted == len(window),
             f"phase {name}: placed {rep.placed} of {len(window)} jobs")
    _require(sched.over_capacity == 0,
             f"phase {name}: {sched.over_capacity} over-capacity placements")
    _require(sched.masked == 0,
             f"phase {name}: {sched.masked} placements on masked arcs")
    peak = loop.stepper.cluster.peak_busy
    _require(bool((peak <= cap).all()),
             f"phase {name}: peak busy {peak.tolist()} over capacity "
             f"{cap.tolist()}")
    _require(out["max_rows"] >= min_rows,
             f"phase {name}: largest round {out['max_rows']} rows < "
             f"{min_rows}")
    _require(ran[kernel] > 0 and not any(ran[m] for m in forbid),
             f"phase {name}: Sinkhorn ran as {ran}; expected {kernel}, "
             f"never {', '.join(forbid)}")
    if reference:
        gaps = flow_gaps(sched)
        served, plan = max(gaps["served"]), max(map(abs, gaps["plan"]))
        control = gaps["control"]
        out.update(flow_rounds=len(gaps["served"]), max_flow_gap=served,
                   max_plan_gap=plan, control_rows=control["rows"],
                   control_plan_gap=control["plan_gap"],
                   control_flow_gap=control["flow_gap"])
        _require(served <= GAP_LIMIT,
                 f"phase {name}: objective gap to flow {served:.4%} over "
                 f"{GAP_LIMIT:.0%}")
        _require(plan <= PLAN_GAP_LIMIT,
                 f"phase {name}: the Sinkhorn plan is {plan:.4%} from the "
                 f"flow optimum, over {PLAN_GAP_LIMIT:.0%}")
        _require(abs(control["plan_gap"]) > PLAN_GAP_LIMIT,
                 f"phase {name}: a cost-blind plan is only "
                 f"{control['plan_gap']:.4%} from the optimum; the plan "
                 f"check cannot tell it from the kernel's")
    return out


class _KeepResults:
    """Executor mixin: keep each cell's engine result on its row, so two
    executors' placements can be compared job by job."""

    def _guarded(self, fn, cell):
        return super()._guarded(
            lambda c: fn(c, return_result=True), cell)


class _SerialKeep(_KeepResults, experiments.SerialExecutor):
    pass


def _device_keep(devices: int):
    from repro.experiments.executor import DeviceExecutor

    class _DeviceKeep(_KeepResults, DeviceExecutor):
        pass

    return _DeviceKeep(devices=devices)


def device_executor_phase(traffic: Traffic, *, cells: int = 8,
                          devices: int = 4) -> dict:
    """An ``cells``-seed plan of ``waterwise[backend=fused]`` cells at
    ``traffic``'s rate, tolerance, fleet load and round period, through
    ``device[devices=N]`` and through ``serial``; placements must match."""
    import jax
    import jax.numpy as jnp

    from repro.core import round as fused_round

    days = traffic.rounds * traffic.window_s / 86400.0
    scenario = (f"diurnal[days={days!r},jobs_per_day={traffic.jobs_per_day!r},"
                f"utilization={traffic.utilization!r},"
                f"window_s={traffic.window_s!r},"
                f"tolerance={traffic.tolerance!r}]")
    plan = experiments.ExperimentPlan.build(
        scenarios=[scenario], policies=["waterwise[backend=fused]"],
        seeds=list(range(cells)))
    batched0 = counter_value("round.batch_solves")
    t0 = time.perf_counter()
    device_rows = _device_keep(devices).run(plan.cells())
    device_s = time.perf_counter() - t0
    batched = counter_value("round.batch_solves") - batched0
    _require(batched > 0, "the device executor batched no solve")
    t0 = time.perf_counter()
    serial_rows = _SerialKeep().run(plan.cells())
    serial_s = time.perf_counter() - t0
    differ = 0
    for d, s in zip(device_rows, serial_rows):
        _require(not d.get("error") and not s.get("error"),
                 f"cell failed: {d.get('error') or s.get('error')}")
        fd, fs = d["_result"]["frame"], s["_result"]["frame"]
        same = all(np.array_equal(fd[k], fs[k])
                   for k in ("job_id", "region", "start_s"))
        differ += not same
    if differ:
        # Before failing, say how far each path lands from the exact flow
        # solver on the same cells.
        flow_plan = experiments.ExperimentPlan.build(
            scenarios=[scenario], policies=["waterwise[backend=flow]"],
            seeds=list(range(cells)))
        flow_rows = experiments.SerialExecutor().run(flow_plan.cells())
        for key in ("carbon_kg", "water_kl"):
            ref = sum(r[key] for r in flow_rows)
            print(f"{key} vs flow: device "
                  f"{sum(r[key] for r in device_rows) / ref - 1:.6e} "
                  f"serial {sum(r[key] for r in serial_rows) / ref - 1:.6e}",
                  flush=True)
    _require(differ == 0, f"{differ} of {cells} cells placed jobs "
             f"differently through device[devices={devices}] and serial")

    # The batch program's mesh: one shard of the cell axis on each device.
    fn = fused_round._batch_program(
        tuple(jax.devices()[:devices]), soften=False, sigma=10.0,
        impl=fused_round.sinkhorn_impl_default(), eps_min=0.005,
        interpret=False)
    shards = fn(jnp.zeros((cells, 3, 7, 6)), jnp.zeros((cells, 7, 2)),
                jnp.zeros((cells, 6)))[0].addressable_shards
    per_device = sorted({int(s.data.shape[0]) for s in shards})
    shard_devices = len({s.device for s in shards})
    _require(shard_devices == devices,
             f"batch mesh spans {shard_devices} device(s), not {devices}")
    return dict(cells=cells, devices=devices, shard_devices=shard_devices,
                cells_per_device=per_device, batched_solves=batched,
                device_s=device_s,
                serial_s=serial_s,
                jobs=int(sum(r["jobs"] for r in serial_rows)))


def _fmt(d: dict) -> str:
    return " ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in d.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the device-executor path on 4 chips")
    ap.add_argument("--rounds", type=int, default=Traffic.rounds,
                    help="served rounds per phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: the JAX backend is {devices[0].platform!r}, not "
              f"a TPU; refusing to run on it", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.runtime.platform import use_compile_cache
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache: {use_compile_cache()}", flush=True)

    traffic = Traffic(rounds=args.rounds)
    with CompileClock() as clock:
        if args.chips == 4:
            res = device_executor_phase(traffic, devices=4)
            print(f"device executor: {_fmt(res)}", flush=True)
        else:
            results = {}
            for name in PHASES:
                warm = name == "C"
                res = run_phase(
                    name, PHASES[name], traffic,
                    kernel="xla" if warm else "pallas",
                    forbid=("pallas_interpret",) if warm
                    else ("pallas_interpret", "xla"),
                    min_rows=MIN_ROWS, reference=not warm, clock=clock)
                results[name] = res
                print(f"phase {name}: {_fmt(res)}", flush=True)
            for key in ("carbon_kg", "water_kl"):
                print(f"{key}: B (cold, recorded) {results['B'][key]!r} "
                      f"vs C (warm) {results['C'][key]!r}")
    print(f"compile: {clock.seconds:.3f} s in all, persistent-cache hits "
          f"{clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
