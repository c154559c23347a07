"""ONE ``Executor`` abstraction, four interchangeable backends.

Every backend maps a list of experiment cells to tidy rows with identical
values — the backend choice is an operational knob (latency, parallelism,
scale), never a semantic one (pinned by parity tests):

* ``serial``   — in-process loop; zero overhead, fully deterministic.
* ``process``  — today's sweep pool: one worker process per *cell* (cells
  are independent and rebuilt from primitives).
* ``sharded``  — splits each *single* cell's trace by arrival time across
  worker processes with engine-state handoff + boundary stitching
  (``repro.experiments.shard``); the scale-out path for 1M+-job cells.
* ``device``   — runs many cells' scheduling rounds as device-parallel
  jitted programs: one engine thread per cell, every ``fused``-backend
  solve intercepted and batched across cells into ONE vmapped /
  shard_mapped dispatch per (bucket, dtype, statics) group
  (``repro.core.round.fused_round_batch``). Cells the batch program cannot
  serve (forecast-driven policies, non-``fused`` solver backends) fall
  back to the serial path, so any plan runs on any backend.

Executors are themselves spec-addressable through the shared grammar —
``"sharded[shards=4,max_workers=4]"`` — with schemas introspected from the
backend constructors, so ``--executor`` CLI flags, plan runners, and tests
all speak the same validated language as policies and scenarios.

A crashed cell never aborts the others on any backend: its row carries the
failure in the ``error`` column and execution continues (the old sweep's
bare ``f.result()`` abort is gone).

One process per chip: a TPU belongs to one process at a time, so worker
processes (``process``, and the ``sharded`` backend's pool) are held to
JAX's CPU backend, and on a TPU host those backends refuse cells that
dispatch device work (:func:`needs_device`) instead of running them on a
CPU fallback in the child. ``serial`` and ``device`` run in-process.
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
import sys
import threading
from typing import Dict, List, Optional, Sequence, Union

import repro.obs as obs
from repro.core import solvers
from repro.core.lockstep import LockstepBatcher
from repro.experiments import runner
from repro.experiments.plan import Cell
from repro.launch import devices as launch_devices
from repro.runtime import platform as runtime_platform
from repro.spec import (Param, parse_raw, params_from_signature,
                        unknown_name_error, validate_params)

def _policy_params(cell: Cell):
    """The cell's policy registry entry and its parameters, defaults filled
    in."""
    from repro import policy
    spec = policy.as_spec(cell.policy)
    entry = policy.get_policy(spec.name)
    params = {name: p.default for name, p in entry.params.items()}
    params.update(spec.params)
    return entry, params


def needs_device(cell: Cell) -> bool:
    """True when running ``cell`` dispatches JAX work: its solver backend,
    or the forecaster of a forecast-driven policy, is registered
    ``on_device``. Anything unclassifiable counts as needing the device."""
    from repro import forecast
    try:
        entry, params = _policy_params(cell)
        if "backend" in params and solvers.on_device(params["backend"]):
            return True
        return bool(entry.forecast_driven
                    and forecast.on_device(params["forecaster"]))
    except Exception:                       # noqa: BLE001 — conservative
        return True


def _device_cells_on_tpu(cells: Sequence[Cell]) -> List[Cell]:
    """The cells that need the device, if this is a TPU host (else none).
    Probes the platform only when such a cell exists."""
    device = [c for c in cells if needs_device(c)]
    return device if device and runtime_platform.on_tpu() else []


def refuse_device_cells(cells: Sequence[Cell], executor: str) -> None:
    """Raise before any worker starts when ``executor`` would hand a cell
    that needs the device to a worker process on a TPU host."""
    device = _device_cells_on_tpu(cells)
    if device:
        names = ", ".join(sorted({str(c.policy) for c in device}))
        raise RuntimeError(
            f"the {executor} executor runs cells in worker processes, and "
            f"on a TPU host the chip belongs to one process: {len(device)} "
            f"cell(s) need the device ({names}); run them with "
            f"--executor serial or --executor 'device[...]'")


def default_executor(cells: Sequence[Cell]) -> str:
    """The sweep default: fan out over processes, except on a TPU host
    when a cell needs the device — then run in-process."""
    return "serial" if _device_cells_on_tpu(cells) else "process"


def _pin_cpu() -> None:
    """Pool-worker initializer: hold the worker to JAX's CPU backend, so it
    can never initialise the accelerator its parent holds."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_platforms", "cpu")


def host_pool(workers: int) -> concurrent.futures.ProcessPoolExecutor:
    """A process pool for host-only work. Workers start CPU-pinned; when
    this process already holds an accelerator backend they are spawned
    fresh rather than forked from it (a forked child would inherit the
    live device client)."""
    ctx = None
    if launch_devices.backend_initialized() \
            and runtime_platform.platform() != "cpu":
        ctx = multiprocessing.get_context("spawn")
    return concurrent.futures.ProcessPoolExecutor(
        workers, mp_context=ctx, initializer=_pin_cpu)


class Executor:
    """Maps cells to tidy rows; subclasses define *where* cells run."""

    name = "?"

    def run(self, cells: List[Cell]) -> List[Dict]:
        raise NotImplementedError

    def _guarded(self, fn, cell: Cell) -> Dict:
        try:
            return fn(cell)
        except Exception as e:              # noqa: BLE001 — error-row contract
            return runner.error_row(cell, e)


class SerialExecutor(Executor):
    """In-process, one cell after another."""

    name = "serial"

    def run(self, cells: List[Cell]) -> List[Dict]:
        return [self._guarded(runner.run_cell, c) for c in cells]


class ProcessExecutor(Executor):
    """One worker process per cell (the classic sweep fan-out).

    ``max_workers=0`` auto-sizes to ``min(cpu_count, len(cells))``. Serial
    and process runs produce identical rows: every cell is deterministic
    in its specs and rebuilt from primitives inside the worker. Workers
    are CPU-pinned; on a TPU host a plan with cells that need the device
    is refused (``refuse_device_cells``).
    """

    name = "process"

    def __init__(self, max_workers: int = 0):
        self.max_workers = int(max_workers)

    def run(self, cells: List[Cell]) -> List[Dict]:
        workers = self.max_workers or min(os.cpu_count() or 1, len(cells))
        if workers <= 1 or len(cells) <= 1:
            return SerialExecutor().run(cells)
        refuse_device_cells(cells, self.name)
        rows: List[Dict] = []
        fn = runner.run_cell_obs if obs.enabled() else runner.run_cell
        with host_pool(workers) as pool:
            futs = [pool.submit(fn, c) for c in cells]
            for cell, fut in zip(cells, futs):
                try:
                    row = fut.result()
                    snap = row.pop("_obs", None)
                    if snap:
                        obs.merge(snap)
                    rows.append(row)
                except Exception as e:      # noqa: BLE001 — error-row contract
                    rows.append(runner.error_row(cell, e))
        return rows


class ShardedExecutor(Executor):
    """Splits each cell's trace across ``shards`` worker slices
    (``repro.experiments.shard``): the single-cell scale-out backend.

    ``shards`` trace slices per cell; ``max_workers=0`` auto-sizes the
    per-cell pool; ``handoff_s=0`` auto-sizes the warm-up handoff window
    from the trace's longest possible in-flight span. Cells run one after
    another — the parallelism lives *inside* each cell.
    """

    name = "sharded"

    def __init__(self, shards: int = 2, max_workers: int = 0,
                 handoff_s: float = 0.0):
        self.shards = int(shards)
        self.max_workers = int(max_workers)
        self.handoff_s = float(handoff_s)

    def run(self, cells: List[Cell]) -> List[Dict]:
        from repro.experiments import shard

        def one(cell: Cell) -> Dict:
            return shard.run_sharded_cell(
                cell, shards=self.shards,
                max_workers=self.max_workers or None,
                handoff_s=self.handoff_s)

        return [self._guarded(one, c) for c in cells]


class DeviceExecutor(Executor):
    """Device-parallel cell execution: one engine thread per cell, the
    cells' fused scheduling solves batched into ONE vmapped/shard_mapped
    XLA dispatch per round wave (``repro.core.round.fused_round_batch``).

    ``devices=0`` auto-sizes to every visible XLA device (configure the
    host split with ``repro.launch.devices.set_host_platform_device_count``
    *before* backend init); ``max_cells=0`` runs all batchable cells as one
    wave, else waves of at most ``max_cells`` threads. Cells whose policy
    cannot batch — forecast-driven pipelines (their fused path pre-solves
    inside pricing) and non-``fused`` solver backends — run on the serial
    path first; rows come back in plan order either way, bit-identical to
    ``serial`` (pinned).
    """

    name = "device"

    def __init__(self, devices: int = 0, max_cells: int = 0):
        self.devices = int(devices)
        self.max_cells = int(max_cells)

    @staticmethod
    def _batchable(cell: Cell) -> bool:
        """True when the cell's every hard/soft solve goes through solver
        backend ``"fused"`` — the one program the batch path serves.
        Forecast-driven policies are excluded even with ``backend=fused``:
        their fused path pre-solves inside pricing (``PricedPlan.presolved``)
        and never reaches ``solvers.solve``, so a barrier slot for them
        could deadlock the wave. Anything unclassifiable is non-batchable
        (clean fallback beats a wrong classification)."""
        try:
            entry, params = _policy_params(cell)
        except Exception:                   # noqa: BLE001 — conservative
            return False
        return not entry.forecast_driven and params.get("backend") == "fused"

    def _run_threaded(self, cell: Cell, i: int, rows: List,
                      batcher: LockstepBatcher) -> None:
        from repro.core.round import SolveRequest

        def hook(cost, allowed, capacity, *, backend, soften, overrun, tol,
                 sigma):
            if backend != "fused":
                return None                 # decline: solve runs in-thread
            return batcher.submit(SolveRequest(
                cost=cost, allowed=allowed, capacity=capacity,
                soften=soften, overrun=overrun, tol=tol, sigma=sigma))

        try:
            with solvers.intercepted(hook):
                rows[i] = self._guarded(runner.run_cell, cell)
        finally:
            batcher.finish()

    def run(self, cells: List[Cell]) -> List[Dict]:
        import jax

        from repro.core import round as fused_round

        avail = len(jax.devices())
        devices = self.devices or avail
        if devices > avail:
            obs.warn("executor.device_clamp",
                     f"device executor asked for {devices} devices but only "
                     f"{avail} XLA device(s) are visible — clamping (set "
                     f"the host split via repro.launch.devices before "
                     f"backend init)")
            devices = avail
        rows: List[Optional[Dict]] = [None] * len(cells)
        batched = [i for i, c in enumerate(cells) if self._batchable(c)]
        serial = [i for i in range(len(cells)) if i not in set(batched)]
        for i in serial:
            rows[i] = self._guarded(runner.run_cell, cells[i])
        wave = self.max_cells or max(len(batched), 1)
        for start in range(0, len(batched), wave):
            chunk = batched[start:start + wave]
            batcher = LockstepBatcher(
                lambda reqs: fused_round.fused_round_batch(
                    reqs, devices=devices))
            threads = []
            for i in chunk:
                batcher.register()
                threads.append(threading.Thread(
                    target=self._run_threaded, args=(cells[i], i, rows,
                                                     batcher),
                    name=f"device-cell-{i}", daemon=True))
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        return rows


_EXECUTORS = {cls.name: cls
              for cls in (SerialExecutor, ProcessExecutor, ShardedExecutor,
                          DeviceExecutor)}

ExecutorLike = Union[str, Executor]


def list_executors() -> List[str]:
    return sorted(_EXECUTORS)


def executor_schema(name: str) -> Dict[str, Param]:
    cls = _EXECUTORS.get(name)
    if cls is None:
        raise unknown_name_error("executor", name, list(_EXECUTORS))
    return {p.name: p
            for p in params_from_signature(cls.__init__, drop_positional=1)}


def get_executor(spec: ExecutorLike, **overrides) -> Executor:
    """Resolve an executor spec — ``"sharded[shards=4]"`` — to a backend
    instance. ``overrides`` (CLI flags; ``None`` values ignored) are
    validated against the backend's introspected schema exactly like any
    other spec params."""
    if isinstance(spec, Executor):
        return spec
    name, raw = parse_raw(spec, kind="executor")
    schema = executor_schema(name)
    merged = dict(raw)
    merged.update({k: v for k, v in overrides.items() if v is not None})
    return _EXECUTORS[name](**validate_params("executor", name, schema,
                                              merged))


def describe_executors() -> str:
    lines = []
    for name in list_executors():
        cls = _EXECUTORS[name]
        doc = (cls.__doc__ or "").strip().splitlines()[0]
        lines.append(f"{name:10s} {doc}")
        for p in executor_schema(name).values():
            lines.append(f"    {p.describe()}")
    return "\n".join(lines)
