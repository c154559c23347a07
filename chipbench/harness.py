"""One benchmark run of one cell: set-up, the measured window, the traced
reduction and the comparison with the reference.

The window drives the program's served path as a time-compressed closed
loop: ``policy.build(spec, tele)`` over an ``EventSimulator``, wrapped in
``serve.DecisionLoop``, whose ``run_round`` is called back to back at
boundaries a round period apart in simulated time. What a round holds
depends only on simulated time and the seed, so a faster program completes
more rounds of the same kind. The service opens at ``t = round_s`` with the
first period's arrivals queued, so every round, the first included, holds
one period of arrivals. Set-up generates the whole job stream, which spans
the configuration's telemetry, and runs the traffic's warm-up rounds; the
window closes with the first round that ends after
``--seconds``, or where the stream ends.

A configuration may state ``fleets`` (1 where absent): independent fleets
served by one process, each with its own policy, engine, ``DecisionLoop``
and job stream (``stream.py`` gives the seeding rule), all over the one
telemetry of the regions they share. At each boundary one loop steps
them all (``fleet_loop``); one ``RoundStat`` is one boundary.

Inside the window the harness keeps only references to what each round
was asked and answered, the answer carrying the transport plan its solve
handed to the host's rounding (``plan_field``); every comparison runs
after the window closes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import math
import os
import pathlib
import shutil
import tempfile
import threading
import time
from typing import List, Optional

import numpy as np

from chipbench import reference, stream as bench_stream, trace as bench_trace

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"


class BenchError(RuntimeError):
    """The cell cannot be run as its files state."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------

def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def config_of(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise BenchError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no traffic mix {name!r} ({path} is missing)")
    return load_json(path)


def limits_of(name: str) -> dict:
    path = HERE / "limits" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no limits for workload {name!r} ({path})")
    return load_json(path)


def metric_reader(name: str):
    """The ``read(run)`` function of ``chipbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones untraced,
    the per-layer ones traced; each only in the cells it lists."""
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def peaks_of(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"chipbench/peaks.json; add its published peaks")
    return table[device_kind]


# ---------------------------------------------------------------------------
# What the window records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Call:
    """One ``schedule`` call: the priced question (index into
    ``Recorder.priced``, None where nothing was priced), the decision and
    the call's ``perf_counter`` span."""
    priced: Optional[int]
    decision: object
    t0: float
    t1: float

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0

    @property
    def plan(self) -> Optional[np.ndarray]:
        """The transport plan the decision's solve handed to the host's
        rounding, read from its result (``plan``, ``plan_mask``), nought
        off the arcs it rounded under; None where no solve rounded one."""
        res = self.decision.solver
        X = getattr(res, "plan", None)
        if X is None:
            return None
        return np.where(res.plan_mask, X, 0.0)


@contextlib.contextmanager
def plan_field():
    """While open, each solve's result carries the plan it rounded.

    Where the program's ``SolveResult`` has the fields ``plan`` and
    ``plan_mask``, the program fills them and nothing is patched.
    Otherwise ``jax_solver._finalize`` sets them on the result it returns,
    by reference, to the plan and mask its call of ``_round_to_vertex``
    received: below any fault planted around ``_finalize``, and per
    thread, so that a solve finalised on another thread, or for another
    fleet, keeps its own plan. A result that no rounding made has None."""
    from repro.core import solvers
    from repro.core.solvers import jax_solver
    fields = {f.name for f in dataclasses.fields(solvers.SolveResult)}
    if {"plan", "plan_mask"} <= fields:
        yield
        return
    seen = threading.local()
    finalize, rounding = jax_solver._finalize, jax_solver._round_to_vertex

    def tapped_rounding(X, cost, mask, capacity):
        seen.plan = (X, mask)
        return rounding(X, cost, mask, capacity)

    def tapped_finalize(*args):
        seen.plan = (None, None)
        res = finalize(*args)
        res.plan, res.plan_mask = seen.plan
        return res

    jax_solver._finalize = tapped_finalize
    jax_solver._round_to_vertex = tapped_rounding
    try:
        yield
    finally:
        jax_solver._finalize, jax_solver._round_to_vertex = finalize, rounding


class Recorder:
    """Delegating scheduler that times ``schedule`` and keeps references to
    each round's question (due jobs, decision time, free servers, the
    priced plan's column count) and its decision."""

    def __init__(self, inner):
        self.inner = inner
        self.priced: list = []
        self.calls: List[Call] = []
        pricer = inner.pricer
        price = pricer.price

        def tapped(jobs, now_s, inst, snap):
            plan = price(jobs, now_s, inst, snap)
            self.priced.append((jobs, now_s, inst.capacity,
                                plan.cost.shape[1]))
            return plan

        pricer.price = tapped

    def schedule(self, jobs, now_s, capacity):
        n0 = len(self.priced)
        t0 = time.perf_counter()
        dec = self.inner.schedule(jobs, now_s, capacity)
        t1 = time.perf_counter()
        self.calls.append(Call(n0 if len(self.priced) > n0 else None, dec,
                               t0, t1))
        return dec

    def __getattr__(self, name):
        return getattr(self.__dict__["inner"], name)


@dataclasses.dataclass
class RoundStat:
    wall_s: float             # one boundary's run_round, every fleet's
    schedule_s: float         # the union of the fleets' schedule() spans


@dataclasses.dataclass
class Fleet:
    """One fleet: its recorded policy and its ``DecisionLoop``, and where
    its records stood when the window opened."""
    rec: Recorder
    loop: object
    calls0: int = 0
    placed0: int = 0
    offered0: int = 0
    shed0: int = 0

    def mark(self) -> None:
        self.calls0, self.placed0 = (len(self.rec.calls),
                                     len(self.loop.stepper.placed))
        self.offered0 = self.loop.admission.offered
        self.shed0 = self.loop.admission.shed

    @property
    def window_calls(self) -> List[Call]:
        return self.rec.calls[self.calls0:]

    @property
    def window_placed(self) -> list:
        return self.loop.stepper.placed[self.placed0:]


class InTurn:
    """Steps every fleet's ``DecisionLoop`` in turn at a boundary, on the
    default device; ``run_round`` returns the wall seconds."""

    def __init__(self, loops):
        self.loops = list(loops)

    def run_round(self, t_k: float) -> float:
        t0 = time.perf_counter()
        for loop in self.loops:
            loop.run_round(t_k)
        return time.perf_counter() - t0


def fleet_loop(loops):
    """What steps several fleets at each boundary: the program's
    ``serve.FleetLoop(loops)`` where it has one (it may batch the fleets'
    solves over the chips), else ``InTurn``."""
    from repro import serve
    return getattr(serve, "FleetLoop", InTurn)(loops)


class CompileCounter:
    """Traces and backend compiles JAX reports while open."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    setup_s: float
    window_s: float
    rounds: List[RoundStat]
    placed: int
    # One per answered call: rows, cols, schedule_s; a traced run adds the
    # seconds of the program's solve spans in the call (span_s), the rows
    # the last of them solved (span_rows) and its Sinkhorn budget (iters).
    solves: List[dict]
    trace: Optional[bench_trace.Reduction] = None
    peaks: Optional[dict] = None


def _verify_policy(sched, config: dict, round_s: float) -> None:
    """The pipeline must run as the configuration states; a departure makes
    the run unsound."""
    o = config["objective"]
    got = dict(lam_co2=sched.lam_co2, lam_h2o=sched.lam_h2o,
               lam_ref=sched.lam_ref, history_window=sched.history.window,
               raw_window=sched.history.raw_window, sigma=sched.sigma)
    want = {k: o[k] for k in got}
    srv = config["server"]
    got.update(embodied_gco2=sched.server.embodied_gco2,
               lifetime_s=sched.server.lifetime_s, backend=sched.backend,
               round_s=sched.round_s, embodied_water_l=np.isclose(
                   sched.server.embodied_water_l,
                   srv["embodied_gco2"] / srv["ci_mfg_g_per_kwh"]
                   * srv["ewif_mfg_l_per_kwh"] * (1 + srv["wsf_mfg"])))
    want.update(embodied_gco2=srv["embodied_gco2"],
                lifetime_s=srv["lifetime_s"], backend="fused",
                round_s=round_s, embodied_water_l=True)
    p = sched.pricer
    if "defer_arc" in config:
        got.update(margin=p.defer_margin, slack_s=p.defer_slack_s)
        want.update(config["defer_arc"])
    if "forecast" in config:
        from repro.forecast import base, holtwinters
        f = config["forecast"]
        grid = [(a, b, g) for a in f["alphas"] for b in f["betas"]
                for g in f["gammas"]]
        got.update(model=p.forecaster_name, horizon_slots=p.horizon_slots,
                   slot_s=p.slot_s, risk=p.risk, defer_eps=p.defer_eps,
                   guard_s=p.guard_s, warmup_hours=p.warmup_hours,
                   warm=p.warm, phi=holtwinters.PHI, z90=base._Z90,
                   period=holtwinters.HoltWinters().period,
                   grid=np.allclose(holtwinters.PARAM_GRID, grid))
        want.update({k: f[k] for k in ("model", "horizon_slots", "slot_s",
                                       "risk", "defer_eps", "guard_s",
                                       "warmup_hours", "phi", "z90",
                                       "period")},
                    warm=False, grid=True)
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise BenchError(f"the policy departs from its configuration: {bad}")


def _tele_arrays(tele) -> dict:
    return dict(ci=tele.ci, ewif=tele.ewif, wue=tele.wue, pue=tele.pue,
                wsf=tele.wsf, bw_gbps=tele.wan_bw_gbps, rtt_s=tele.wan_rtt_s)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

# Rounds end this share of a period past each multiple of the round period.
PHASE = 0.5


def deadline_s(job) -> float:
    """The last instant a job may finish: ``submit + (1 + tolerance) ·
    exec``."""
    return job.submit_time_s + (1.0 + job.tolerance) * job.exec_time_s


def first_decision_s(submit_s: float, round_s: float) -> float:
    """The engine loop instant at which a job submitted at ``submit_s`` is
    first decided: the loop opens at ``round_s`` and steps ``round_s``
    (``SimConfig(window_s=round_s)``), taking every job submitted at or
    before its instant."""
    return max(round_s, math.ceil(submit_s / round_s) * round_s)


def late_on_arrival(job, round_s: float) -> bool:
    """Whether a job's deadline has passed before its first decision could
    start it: even started at that instant on its home region, where
    transfer takes no time, it would finish late. Read from the job's own
    fields and the loop's instants alone, never from what the program
    decided."""
    return (first_decision_s(job.submit_time_s, round_s) + job.exec_time_s
            > deadline_s(job) + 1e-6)


def run_cell(*, config: dict, traffic: dict, limits: dict, metrics: list,
             seed: int, seconds: float, traced: bool, t_start: float,
             device: dict) -> dict:
    """One run (module docstring). ``t_start`` is the ``perf_counter``
    instant the process started; ``device`` names the chip. Returns the
    result line as a dict, its ``checks`` last."""
    from repro import policy
    from repro.core import telemetry
    from repro.serve import DecisionLoop, ServeConfig
    from repro.sim.cluster import Cluster
    from repro.sim.engine import EngineState, EventSimulator, SimConfig

    round_s = float(traffic["round_s"])
    R = config["regions"]
    n_fleets = int(config.get("fleets", 1))
    if n_fleets < 1:
        raise BenchError(f"the configuration states {n_fleets} fleets")
    tele = telemetry.generate(**config["telemetry"])
    if tele.num_regions != R:
        raise BenchError(f"telemetry has {tele.num_regions} regions, the "
                         f"configuration states {R}")
    t_stream = time.perf_counter()
    srcs = [bench_stream.Stream.from_traffic(
        traffic, seed, R, span_s=tele.ci.shape[0] * 3600.0, phase=PHASE,
        fleet=i) for i in range(n_fleets)]
    stream_s = time.perf_counter() - t_stream
    cap = np.full(R, int(config["servers_per_region"]), np.int64)
    fleets = []
    for src in srcs:
        rec = Recorder(policy.build(config["policy"], tele))
        sim = EventSimulator(tele, cap, SimConfig(window_s=round_s))
        loop = DecisionLoop(sim, rec, src,
                            ServeConfig(round_s=round_s,
                                        queue_bound=10 ** 9))
        loop.stepper = sim.stepper(rec, state=EngineState(
            now=round_s, pending=[], applied_events=0,
            cluster=Cluster(cap).export_state()))
        _verify_policy(rec.inner, config, round_s)
        fleets.append(Fleet(rec, loop))
    serve_loop = (fleets[0].loop if n_fleets == 1
              else fleet_loop([f.loop for f in fleets]))

    def boundary(k: int) -> float:
        return (k + PHASE) * round_s

    with plan_field():
        t_warm = time.perf_counter()
        k = _warm_up(serve_loop, fleets, boundary,
                     int(traffic["warmup_rounds"]))
        warmup_s = time.perf_counter() - t_warm
        gc.collect()
        gc.freeze()         # the stream and set-up stay out of collections
        setup_s = time.perf_counter() - t_start
        try:
            win = _window(serve_loop, fleets, boundary, k, seconds, traced,
                          min(src.span_s for src in srcs))
        finally:
            gc.unfreeze()

    import jax
    device = dict(device)
    chips = device.pop("chips", 1)
    mem = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    device["memory_peak_bytes"] = int(max(m.get("peak_bytes_in_use", 0)
                                          for m in mem))

    placed = [p for f in fleets for p in f.window_placed]
    solves = []
    for f in fleets:
        for c in f.window_calls:
            if c.decision.solver is None:
                continue
            cols = R if c.decision.softened else f.rec.priced[c.priced][3]
            solves.append(dict(rows=int(c.decision.solver.assign.shape[0]),
                               cols=int(cols), schedule_s=c.wall_s, t0=c.t0,
                               t1=c.t1))
    solves.sort(key=lambda sv: sv["t0"])
    run = Run(setup_s=setup_s, window_s=win.window_s, rounds=win.rounds,
              placed=len(placed), solves=solves)
    if traced:
        _attach_trace(run, win.trace_files, device, chips)

    out_metrics = {}
    for m in metrics:
        v = metric_reader(m["name"])(run)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # A job placed past its deadline is the program's failure, unless its
    # deadline had passed before the loop could first decide it.
    late = [job for job, _, _, finish in placed
            if finish > deadline_s(job) + 1e-6]
    unreachable = sum(late_on_arrival(job, round_s) for job in late)
    t_ref = time.perf_counter()
    checks, readings = compare(fleets, tele, config, round_s, limits,
                               int(traffic["reference_rounds"]), seed,
                               win.compiles)
    readings["reference_s"] = time.perf_counter() - t_ref
    readings["late"] = len(late)
    readings["late_on_arrival"] = unreachable
    readings["stream_end"] = win.stream_end
    readings["setup_stream_s"] = stream_s
    readings["setup_warmup_s"] = warmup_s
    result = dict(correct=all(c["value"] <= c["limit"]
                              for c in checks.values()),
                  attempted=sum(f.loop.admission.offered - f.offered0
                                for f in fleets),
                  failed=sum(f.loop.admission.shed - f.shed0
                             for f in fleets) + len(late) - unreachable,
                  metrics=out_metrics, device=device)
    if traced:
        result["device"].update(busy_s=run.trace.busy_s,
                                window_s=run.trace.window_s)
        result["breakdown"] = dict(
            device_ops=[[n, s] for n, s in run.trace.top_ops],
            idle_gaps=[[n, s] for n, s in run.trace.idle_gaps])
    result["readings"] = readings
    result["checks"] = checks
    return result


def _warm_up(serve_loop, fleets: List[Fleet], boundary,
             rounds: int) -> int:
    """``rounds`` rounds, then, for each fleet, the Eqs 12-13 soft
    fallback's program at its last round's size, which a round takes where
    a job arrives with its deadline already out of reach. Returns the last
    boundary index run."""
    from repro.core import solvers
    for k in range(1, rounds + 1):
        serve_loop.run_round(boundary(k))
    for f in fleets:
        if not f.rec.priced:
            continue
        jobs, _, capacity, _ = f.rec.priced[-1]
        M, R = len(jobs), len(capacity)
        sched = f.rec.inner
        solvers.solve(np.ones((M, R)), np.ones((M, R), bool),
                      np.full(R, M, np.int64), backend=sched.backend,
                      soften=True, overrun=np.zeros((M, R)),
                      tol=np.full(M, 0.5), sigma=sched.sigma)
    return rounds


@dataclasses.dataclass
class Window:
    rounds: List[RoundStat]
    window_s: float
    trace_files: Optional[tuple]
    stream_end: bool
    compiles: int


def _window(serve_loop, fleets: List[Fleet], boundary, k: int,
            seconds: float, traced: bool, span_s: float) -> Window:
    """The measured window, from boundary ``k + 1`` on, to the stream's
    end at ``span_s`` at the latest."""
    for f in fleets:
        f.mark()
    trace_files = None
    if traced:
        import jax
        import repro.obs as obs
        tmp = tempfile.mkdtemp(prefix="chipbench-")
        obs.enable(trace_path=os.path.join(tmp, "obs.jsonl"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(bench_trace.ANCHOR):
            pass
    rounds: List[RoundStat] = []
    stream_end = False
    with CompileCounter() as compiles:
        t_win = time.perf_counter()
        while True:
            k += 1
            if boundary(k) > span_s:
                stream_end = True
                break
            n_calls = [len(f.rec.calls) for f in fleets]
            t0 = time.perf_counter()
            serve_loop.run_round(boundary(k))
            t1 = time.perf_counter()
            calls = bench_trace.union(
                ((c.t0, c.t1) for f, n in zip(fleets, n_calls)
                 for c in f.rec.calls[n:]), -math.inf, math.inf)
            rounds.append(RoundStat(t1 - t0, sum(e - s for s, e in calls)))
            if t1 - t_win >= seconds:
                break
    t_end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
        t0_perf = obs.tracer()._t0
        obs.disable()
        spans = bench_trace.load_spans(os.path.join(tmp, "obs.jsonl"),
                                       t0_perf)
        trace_files = (tmp, anchor, t_win, t_end, spans)
    return Window(rounds, t_end - t_win, trace_files, stream_end,
                  compiles.count)


SOLVE_SPANS = ("solver.solve", "solver.fused_round")


def _attach_trace(run: Run, trace_files, device: dict, chips: int) -> None:
    tmp, anchor, t_win, t_end, spans = trace_files
    try:
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise BenchError("the profiler wrote no trace")
        events = bench_trace.load_xplane(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    offset = bench_trace.anchor_offset_ns(events, anchor)
    if offset is None:
        raise BenchError("the trace lacks its anchor annotation")

    def to_ns(perf_s):
        return perf_s * 1e9 + offset

    run.trace = bench_trace.reduce(events, to_ns(t_win), to_ns(t_end),
                                   spans=spans, perf_to_trace_ns=to_ns,
                                   chips=chips)
    run.peaks = peaks_of(device["kind"])
    # The program's solve spans inside each answered call: their seconds,
    # and the rows and Sinkhorn budget of the last, which gave the answer.
    solve_spans = sorted((s, e, args) for name, s, e, args in spans
                         if name in SOLVE_SPANS)
    for solve in run.solves:
        inside = [(s, e, a) for s, e, a in solve_spans
                  if solve["t0"] <= s and e <= solve["t1"]]
        if not inside:
            continue
        solve["span_s"] = sum(e - s for s, e, _ in inside)
        args = inside[-1][2]
        if "jobs" in args:
            solve["span_rows"] = int(args["jobs"])
        if "sinkhorn_iters" in args:
            solve["iters"] = int(args["sinkhorn_iters"])


# ---------------------------------------------------------------------------
# The comparison that decides ``correct``
# ---------------------------------------------------------------------------

EXACT = ("no_answer", "mode", "unassigned", "over_capacity", "masked",
         "window_compiles")


def compare(fleets: List[Fleet], tele, config: dict, round_s: float,
            limits: dict, sample: int, seed: int, compiles: int) -> dict:
    """Hold every round the window answered, in every fleet, against the
    reference, and a sample of them drawn from the seed over all fleets'
    rounds pooled, the largest among them, against its exact optimum. Each
    fleet's rounds are priced on its own history. Returns the checks,
    {name: {"value", "limit"}}: the exact counts summed over the fleets
    with limit 0, then each gap, the largest over the fleets, with the
    limit the cell's file gives; and the readings compared with no limit
    (a gap the control does not separate, how many rounds were held
    against the reference, the number of fleets)."""
    ref = reference.Reference(_tele_arrays(tele), config, round_s)
    counts = dict.fromkeys(EXACT, 0)
    counts["window_compiles"] = compiles
    rounds = []             # (the fleet's decision times, index, Round)
    for f in fleets:
        observed = [p[1] for p in f.rec.priced]
        answered = [c for c in f.window_calls if c.priced is not None]
        counts["no_answer"] += int(not answered)
        for c in answered:
            jobs, now_s, capacity, _ = f.rec.priced[c.priced]
            r = reference.Round.of(jobs, now_s, capacity,
                                   c.decision.solver.assign,
                                   c.decision.softened, c.plan)
            inst = ref.instance(r, observed[:c.priced + 1], with_cost=False)
            for k, v in reference.check_round(inst, r).items():
                counts[k] += v
            rounds.append((observed, c.priced, r))
    # The gaps compare hard rounds, a sample drawn from the seed with the
    # largest among them. A soft (Eqs 12-13) round folds penalties of up to
    # sigma times the overrun into its costs, and the kernel resolves its
    # normalised base costs only coarsely, so the few soft rounds of a
    # window are sampled alike, but reported apart.
    hard = [x for x in rounds if not x[2].softened]
    soft = [x for x in rounds if x[2].softened]
    picks = reference.sample_rounds([len(x[2].t) for x in hard], sample,
                                    seed)
    worst = dict.fromkeys(("plan_gap", "served_gap"),
                          0.0 if picks else math.inf)
    soft_worst = dict.fromkeys(("soft_plan_gap", "soft_served_gap"), 0.0)
    for group, out in ((hard, worst), (soft, soft_worst)):
        idxs = picks if group is hard else reference.sample_rounds(
            [len(x[2].t) for x in soft], sample, seed)
        for i in idxs:
            observed, idx, r = group[i]
            inst = ref.instance(r, observed[:idx + 1], with_cost=True)
            g = reference.gaps(inst, r)
            if g is None:
                counts["no_answer"] += 1
                continue
            for k in out:
                out[k] = max(out[k], g[k.replace("soft_", "")])
    checks = {k: {"value": v, "limit": 0} for k, v in counts.items()}
    for k, lim in limits.items():
        checks[k] = {"value": worst[k], "limit": lim}
    readings = {k: v for k, v in worst.items() if k not in limits}
    readings["rounds_checked"] = len(rounds)
    readings["rounds_solved_exactly"] = len(picks)
    readings["soft_rounds"] = len(soft)
    readings.update(soft_worst)
    readings["fleets"] = len(fleets)
    return checks, readings
