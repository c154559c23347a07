"""One fused scheduling round: pricing → masking → Sinkhorn → extraction
as a SINGLE jitted XLA program.

The hot path of a WaterWise scheduling round used to be several separately-
jitted pieces with host round-trips between them: ``problem.build`` /
``forecast.planner.build_temporal_plan`` priced the (jobs × regions × slots)
grid in numpy, ``jax_solver._prepare`` normalized and padded on the host,
``sinkhorn_log`` ran on device, the duals came back to the host, went *back*
to the device for ``plan_from_duals``, and the plan returned once more for
rounding. This module fuses everything between the raw per-round tensors and
the (host-side, inherently sequential) greedy vertex rounding into one XLA
computation:

  ``_assignment_program``   soft-cost folding → arc masking → cost
                            normalization → balanced-OT reduction → annealed
                            log-domain Sinkhorn → plan extraction, one jit.
                            Registered as solver backend ``"fused"`` — a
                            drop-in for ``"jax"`` everywhere a backend name
                            is accepted (``waterwise[backend=fused]``).
  ``_temporal_program``     additionally fuses the *pricing* of the
                            jobs × (regions × slots) decision grid (paper
                            Eqs 1-8 via ``core.footprint``, which is pure
                            arithmetic and traces transparently) and the
                            deadline-feasibility masking (Eq 11 + guard)
                            into the same program. Driven by
                            ``ForecastPricer`` when the pipeline backend is
                            ``"fused"`` (``waterwise-forecast[backend=fused]``).

Round-trip discipline — the actual perf content of the fusion:

  * everything that varies per round is packed into one contiguous per-job
    blob plus one small region-attribute array, so a temporal round costs
    TWO host→device copies instead of ~20 small ones;
  * per-pipeline constants (λ weights, guard, slot offsets, server spec)
    are compile-time static — zero per-round transfer;
  * inputs are padded on the HOST to the row buckets of
    ``jax_solver.BUCKETS`` and the true job count rides along inside a
    traced array, so a whole simulation — thousands of rounds with jittery
    window sizes — compiles each program once per bucket, exactly like the
    unfused path (padding rows carry zero log-domain mass and are exact
    no-ops in every Sinkhorn update);
  * only the normalized costs and the extracted plan return to the host
    (one transfer); the priced cost/mask tensors stay on device unless the
    caller records windows for offline replay.

The Sinkhorn inner loop runs the XLA scan of ``jax_solver`` by default and
can run the fused Pallas row/col-reduction kernel
(``repro.kernels.sinkhorn``) instead where shapes allow — auto-selected on
TPU, opt-in elsewhere (interpret mode is for validation, not speed).

Parity contract (pinned in tests/test_round.py): for identical inputs the
fused and unfused paths produce **bit-identical scheduling decisions** —
the same assignment vector, hold/defer split, and feasibility status per
round, and therefore bit-identical engine records end-to-end. Dual
potentials may differ in low-order bits (the fused program normalizes in
float32 on device where the unfused path staged through float64 numpy), but
the decisions they round to are pinned equal per dtype/shape bucket.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import footprint, problem, solvers
from repro.core.solvers import jax_solver
from repro.core.solvers.jax_solver import BIG, _NEG, bucket_for
from repro.runtime import platform as runtime_platform

__all__ = ["fused_solve", "fused_temporal_round", "fused_round_batch",
           "sinkhorn_impl_default", "SinkhornWarmStart", "SolveRequest",
           "group_requests"]


def sinkhorn_impl_default() -> str:
    """``pallas`` on TPU (the fused row/col-reduction kernel), ``xla``
    elsewhere (interpret-mode Pallas is a validation path, not a fast one)."""
    return "pallas" if runtime_platform.on_tpu() else "xla"


def _pad_rows(rows: int):
    """(bucket, job-row pad): job tensors are padded to ``bucket − 1`` rows
    so that [padded jobs | dummy slack row] fills the bucket exactly."""
    bucket = bucket_for(rows + 1)
    return bucket, bucket - 1 - rows


def _pad0(x, pad: int, value=0):
    """Pad job-axis tensors with ``pad`` constant rows."""
    if pad == 0:
        return x
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return np.pad(x, width, constant_values=value)


def _interpret(impl: str, interpret: Optional[bool]) -> bool:
    """Interpret mode applies to the Pallas kernel only, and defaults to on
    exactly when the backend is not a TPU."""
    if impl != "pallas":
        return False
    if interpret is not None:
        return bool(interpret)
    return not runtime_platform.on_tpu()


def _count_sinkhorn(impl: str, interpret: bool, n: int = 1) -> None:
    """Count solves by the Sinkhorn implementation that actually ran:
    ``round.sinkhorn/pallas`` (the compiled kernel), ``/pallas_interpret``
    or ``/xla``. Always live, like every ``obs`` counter, so a run can
    prove which kernel served it without enabling tracing."""
    mode = "pallas_interpret" if impl == "pallas" and interpret else impl
    obs.counter(f"round.sinkhorn/{mode}", n)


# ---------------------------------------------------------------------------
# Fused inner stages (traced pieces shared by both programs)
# ---------------------------------------------------------------------------

def _prepare_device(c_eff, mask, cap, valid):
    """Traced equivalent of ``jax_solver._prepare``: normalize costs to
    ~unit scale, price forbidden arcs at BIG, append the balanced-OT dummy
    supply row. ``valid`` marks real job rows; padding rows get zero mass
    (log marginal ``_NEG``) and are exact no-ops in the log-domain updates."""
    Mb, N = c_eff.shape
    scale = jnp.maximum(jnp.max(jnp.where(mask, jnp.abs(c_eff), 0.0)), 1e-9)
    Cn = jnp.where(mask, c_eff / scale, BIG).astype(jnp.float32)
    C = jnp.concatenate([Cn, jnp.zeros((1, N), jnp.float32)], axis=0)
    m_true = valid.sum()
    slack = jnp.maximum(cap.sum() - m_true, 1e-9)
    total = m_true + slack
    log_a = jnp.concatenate([
        jnp.where(valid, -jnp.log(total), _NEG),
        jnp.log(slack / total)[None]]).astype(jnp.float32)
    log_b = jnp.log(jnp.maximum(cap, 1e-12) / total).astype(jnp.float32)
    return C, log_a, log_b, Cn, scale


def _sinkhorn_pallas(C, log_a, log_b, *, eps0: float, eps_min: float,
                     iters: int, anneal_stages: int, interpret: bool):
    """ε-annealed Sinkhorn with the fused Pallas iteration kernel as the
    inner loop. The kernel's ε is a compile-time constant, so the anneal
    schedule is unrolled in Python (``anneal_stages`` is static and small)
    with one ``fori_loop`` per stage. The kernel updates (f ← g, then
    g ← f) where the XLA path updates (g ← f, then f ← g); both converge
    to the same transport polytope vertex as ε → 0."""
    from repro.kernels.sinkhorn.ops import sinkhorn_iteration
    decay = (eps_min / eps0) ** (1.0 / max(anneal_stages - 1, 1))
    f = jnp.zeros(C.shape[0], jnp.float32)
    g = jnp.zeros(C.shape[1], jnp.float32)
    eps = eps0
    for s in range(anneal_stages):
        eps = eps0 * decay ** s

        def body(_, fg, _eps=eps):
            return sinkhorn_iteration(C, fg[0], fg[1], log_a, log_b, _eps,
                                      interpret=interpret)

        f, g = jax.lax.fori_loop(0, iters, body, (f, g))
    return f, g, eps


def _solve_core(c_eff, mask, cap, valid, *, impl: str, eps0: float,
                eps_min: float, iters: int, anneal_stages: int,
                interpret: bool):
    """prepare → annealed Sinkhorn → plan extraction, all traced. Returns
    the (padded-row) normalized cost matrix, row-normalized plan, and the
    normalization scale; the host slices off the padding."""
    C, log_a, log_b, Cn, scale = _prepare_device(c_eff, mask, cap, valid)
    if impl == "pallas":
        f, g, eps = _sinkhorn_pallas(C, log_a, log_b, eps0=eps0,
                                     eps_min=eps_min, iters=iters,
                                     anneal_stages=anneal_stages,
                                     interpret=interpret)
    else:
        f, g, eps = jax_solver._sinkhorn_log_impl(
            C, log_a, log_b, eps0, eps_min, iters, anneal_stages)
    X = jnp.exp((f[:, None] + g[None, :] - C) / eps)[:Cn.shape[0]]
    X = X / jnp.maximum(X.sum(axis=1, keepdims=True), 1e-30)
    return Cn, X, scale


# ---------------------------------------------------------------------------
# Program 1: the fused assignment solve (solver backend "fused")
# ---------------------------------------------------------------------------

def _assignment_body(arcs, tolv, cap, *, soften: bool, sigma: float,
                     impl: str, eps0: float = 0.5, eps_min: float = 0.005,
                     iters: int = 60, anneal_stages: int = 6,
                     interpret: bool = False):
    """Soft-cost folding + masking + prepare + Sinkhorn + extraction as one
    XLA computation (the device half of the ``"fused"`` backend).

    ``arcs`` packs [cost | allowed(0/1) | overrun] as one [3, Mb, C] upload;
    ``tolv`` packs [tol | row-validity] as [Mb, 2] — bucket-padded, with the
    true job count implied by the validity column.

    Unjitted on purpose: the single-cell program jits it directly
    (``_assignment_program``) and the device-parallel batch path vmaps /
    shard_maps the *same traced body* over a leading cell axis
    (``fused_round_batch``) — per-cell results are bitwise identical by
    construction (pinned in tests/test_device_executor.py).
    """
    cost, allowed, overrun = arcs[0], arcs[1] > 0.5, arcs[2]
    tol, valid = tolv[:, 0], tolv[:, 1] > 0.5
    if soften:
        excess = jnp.maximum(overrun - tol[:, None], 0.0)
        c_eff = cost + sigma * excess
        mask = valid[:, None] & jnp.ones_like(allowed)
    else:
        c_eff = cost
        mask = valid[:, None] & allowed
    Cn, X, _ = _solve_core(c_eff, mask, cap, valid, impl=impl, eps0=eps0,
                           eps_min=eps_min, iters=iters,
                           anneal_stages=anneal_stages, interpret=interpret)
    return Cn, X


_assignment_program = functools.partial(jax.jit, static_argnames=(
    "soften", "sigma", "impl", "eps0", "eps_min", "iters", "anneal_stages",
    "interpret"))(_assignment_body)


@solvers.register("fused", on_device=True)
def fused_solve(cost: np.ndarray, allowed: np.ndarray, capacity: np.ndarray,
                *, soften: bool = False,
                overrun: Optional[np.ndarray] = None,
                tol: Optional[np.ndarray] = None, sigma: float = 10.0,
                eps_min: float = 0.005,
                sinkhorn_impl: Optional[str] = None,
                interpret: Optional[bool] = None) -> solvers.SolveResult:
    """Drop-in ``"jax"``-backend replacement with the device work fused
    into one program: ONE dispatch and ONE host transfer per round instead
    of host prepare → Sinkhorn → host → plan extraction → host. The greedy
    vertex rounding + exact SSP repair + 2-swap polish stay on the host
    (inherently sequential, microseconds at scheduling sizes)."""
    def run() -> solvers.SolveResult:
        M, N = cost.shape
        cap = capacity.astype(np.int64)
        if int(cap.sum()) < M or \
                not (soften or allowed.any(axis=1).all()):
            return _infeasible(M)
        _, pad = _pad_rows(M)
        impl = sinkhorn_impl or sinkhorn_impl_default()
        interp = _interpret(impl, interpret)
        with obs.span("solver.pack"):
            arcs = np.stack([
                _pad0(cost, pad),
                _pad0(allowed.astype(np.float64), pad),
                _pad0(overrun if overrun is not None else np.zeros((M, N)),
                      pad)]).astype(np.float32)
            tolv = np.stack([
                _pad0(tol if tol is not None else np.zeros(M), pad),
                _pad0(np.ones(M), pad)], axis=1).astype(np.float32)
        with obs.span("solver.device"):
            Cn, X = jax_solver.fetch(_assignment_program(
                jnp.asarray(arcs), jnp.asarray(tolv),
                jnp.asarray(cap, jnp.float32),
                soften=bool(soften), sigma=float(sigma), impl=impl,
                eps_min=float(eps_min), interpret=interp))
        _count_sinkhorn(impl, interp)
        if obs.enabled():
            bucket = M + 1 + pad
            obs.annotate(
                bucket=bucket, pad=pad, occupancy=(M + 1) / bucket,
                sinkhorn_iters=jax_solver.SINKHORN_ITERS
                * jax_solver.SINKHORN_STAGES,
                eps0=jax_solver.SINKHORN_EPS0, eps_min=eps_min,
                anneal_stages=jax_solver.SINKHORN_STAGES, impl=impl,
                interpret=interp)
        c_eff, mask = jax_solver._effective(cost, allowed, soften, overrun,
                                            tol, sigma)
        res = jax_solver._finalize(np.asarray(X[:M], np.float64),
                                   np.asarray(Cn[:M], np.float64), c_eff,
                                   mask, cap, soften, overrun, tol)
        res.backend = "fused"
        return res
    return solvers._timed(run)


def _infeasible(M: int) -> solvers.SolveResult:
    res = jax_solver._infeasible(M)
    res.backend = "fused"
    return res


# ---------------------------------------------------------------------------
# Program 1b: the device-parallel batched assignment solve
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SolveRequest:
    """One cell's assignment-round solve, queued for device-parallel
    batching (``fused_round_batch``). Fields mirror ``fused_solve``'s
    signature — a request is exactly one deferred call."""
    cost: np.ndarray                       # [M, C]
    allowed: np.ndarray                    # [M, C]
    capacity: np.ndarray                   # [C]
    soften: bool = False
    overrun: Optional[np.ndarray] = None
    tol: Optional[np.ndarray] = None
    sigma: float = 10.0
    eps_min: float = 0.005
    sinkhorn_impl: Optional[str] = None
    interpret: Optional[bool] = None


def group_requests(requests) -> dict:
    """Group request *indices* by compile signature: (row bucket, columns,
    cost dtype, soften, sigma, impl, eps_min, interpret).

    Pure bookkeeping (property-tested): a group never mixes row buckets,
    column counts, dtypes, or solver statics — each group maps onto exactly
    one compiled batch program, and one compile serves every batch that
    shares the signature.
    """
    groups: dict = {}
    for i, r in enumerate(requests):
        M, C = np.asarray(r.cost).shape
        key = (bucket_for(M + 1), C, np.dtype(np.asarray(r.cost).dtype).str,
               bool(r.soften), float(r.sigma), r.sinkhorn_impl,
               float(r.eps_min), r.interpret)
        groups.setdefault(key, []).append(i)
    return groups


def _request_statics(req: SolveRequest) -> dict:
    """The resolved static (compile-time) solver constants of one request —
    identical across a group by construction of the group key."""
    impl = req.sinkhorn_impl or sinkhorn_impl_default()
    return dict(soften=bool(req.soften), sigma=float(req.sigma), impl=impl,
                eps_min=float(req.eps_min),
                interpret=_interpret(impl, req.interpret))


@functools.lru_cache(maxsize=None)
def _batch_program(devices: tuple, *, soften: bool, sigma: float, impl: str,
                   eps_min: float, interpret: bool):
    """The compiled device-parallel batch program for one static signature:
    ``vmap`` of the single-cell ``_assignment_body`` over a leading cell
    axis, ``shard_map``-split across the XLA ``devices`` (a tuple) when
    there is more than one. Cached per (devices, statics) — jitted shapes
    cache underneath as usual."""
    one = functools.partial(_assignment_body, soften=soften, sigma=sigma,
                            impl=impl, eps_min=eps_min, interpret=interpret)
    fn = jax.vmap(one)
    if len(devices) > 1:
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("cells",))
        fn = jax.shard_map(fn, mesh=mesh,
                           in_specs=(P("cells"), P("cells"), P("cells")),
                           out_specs=(P("cells"), P("cells")),
                           check_vma=False)
    return jax.jit(fn)


def _batch_size(n: int, devices: int) -> int:
    """Compiled batch size for ``n`` cells: next power of two per device
    shard × the device count, so jittery group sizes reuse a handful of
    compiled batch shapes (the cell-axis analogue of the row buckets)."""
    per = -(-n // devices)
    p = 1
    while p < per:
        p *= 2
    return devices * p


def fused_round_batch(requests, devices: int = 1,
                      slots: Optional[int] = None) -> list:
    """Solve many independent cells' assignment rounds as device-parallel
    jitted programs — ONE dispatch per (bucket, dtype, statics) group
    instead of one per cell.

    The batch path vmaps (and, with ``devices > 1``, shard_maps over a
    host-device mesh) the exact traced body the single-cell ``"fused"``
    backend jits, with identical per-cell bucket padding — so every cell's
    normalized costs and transport plan are **bitwise identical** to a
    per-cell ``fused_solve`` call (pinned in tests/test_device_executor.py),
    and the host-side vertex rounding consumes identical inputs. Groups are
    padded by repeating the last cell (results sliced off) to ``slots``
    cells, a multiple of ``devices``, so that each row bucket has one
    compiled batch shape whichever cells share it; without ``slots``, to
    ``_batch_size``, which keeps compiled batch shapes few and device
    shards equal-sized.

    Returns ``SolveResult``s in request order; per-request infeasibility
    (capacity shortfall / fully masked row) short-circuits exactly like
    ``fused_solve``. Span ``solver.round_batch`` holds the call, annotated
    with its true rows (jobs plus the balancing row), the padded rows the
    device solved (bucket padding and repeated slots included), the slots
    and the chips; span ``solver.batch_device`` each group's dispatch
    through its copy to the host. ``obs`` counters: ``round.batch_compile``
    counts fresh program compiles (retrace accounting for the bench gate),
    ``round.batch_solves`` cells served, and ``solver.batch_rows``,
    ``solver.batch_padded_rows``, ``solver.batch_sinkhorn_iters`` (Sinkhorn
    iterations over the cells served) and ``solver.batch_chips`` (chips
    per group dispatch) the work.
    """
    devices = max(1, int(devices))
    n_avail = len(jax.devices())
    if devices > n_avail:
        raise ValueError(f"devices={devices} exceeds the {n_avail} "
                         f"available XLA device(s)")
    if slots is not None and (slots < 1 or slots % devices):
        raise ValueError(f"slots={slots} is not a positive multiple of "
                         f"devices={devices}")
    results: list = [None] * len(requests)
    live: list = []
    for i, r in enumerate(requests):
        M, C = r.cost.shape
        cap = np.asarray(r.capacity).astype(np.int64)
        allowed = np.asarray(r.allowed, bool)
        if int(cap.sum()) < M or \
                not (r.soften or allowed.any(axis=1).all()):
            results[i] = _infeasible(M)
        else:
            live.append(i)
    if not live:
        return results
    groups = group_requests([requests[i] for i in live])
    iters = jax_solver.SINKHORN_ITERS * jax_solver.SINKHORN_STAGES
    rows = padded = n_slots = 0
    with obs.timed("solver.round_batch", requests=len(requests),
                   groups=len(groups), devices=devices) as t:
        for key, local in groups.items():
            idxs = [live[j] for j in local]
            bucket = key[0]
            statics = _request_statics(requests[idxs[0]])
            arcs_l, tolv_l, cap_l = [], [], []
            for i in idxs:
                r = requests[i]
                M, C = r.cost.shape
                pad = bucket - 1 - M
                arcs_l.append(np.stack([
                    _pad0(r.cost, pad),
                    _pad0(np.asarray(r.allowed).astype(np.float64), pad),
                    _pad0(r.overrun if r.overrun is not None
                          else np.zeros((M, C)), pad)]).astype(np.float32))
                tolv_l.append(np.stack([
                    _pad0(r.tol if r.tol is not None else np.zeros(M), pad),
                    _pad0(np.ones(M), pad)], axis=1).astype(np.float32))
                cap_l.append(np.asarray(r.capacity).astype(np.int64)
                             .astype(np.float32))
                rows += M + 1
            B = len(idxs)
            S = _batch_size(B, devices) if slots is None else slots
            if B > S:
                raise ValueError(f"{B} cells share a row bucket, more than "
                                 f"slots={slots}")
            for _ in range(S - B):
                arcs_l.append(arcs_l[-1])
                tolv_l.append(tolv_l[-1])
                cap_l.append(cap_l[-1])
            padded += bucket * S
            n_slots += S
            fn = _batch_program(tuple(jax.devices()[:devices]), **statics)
            before = fn._cache_size()
            args = (np.stack(arcs_l), np.stack(tolv_l), np.stack(cap_l))
            with obs.span("solver.batch_device", bucket=bucket, cells=B,
                          slots=S, chips=devices):
                Cnb, Xb = jax_solver.fetch(fn(*map(jnp.asarray, args)))
            compiles = fn._cache_size() - before
            if compiles:
                obs.counter("round.batch_compile", compiles)
            _count_sinkhorn(statics["impl"], statics["interpret"], B)
            obs.counter("solver.batch_sinkhorn_iters", B * iters)
            obs.counter("solver.batch_chips", devices)
            for b, i in enumerate(idxs):
                r = requests[i]
                M = r.cost.shape[0]
                cap = np.asarray(r.capacity).astype(np.int64)
                c_eff, mask = jax_solver._effective(
                    np.asarray(r.cost, np.float64),
                    np.asarray(r.allowed, bool), r.soften, r.overrun,
                    r.tol, r.sigma)
                res = jax_solver._finalize(
                    np.asarray(Xb[b][:M], np.float64),
                    np.asarray(Cnb[b][:M], np.float64), c_eff, mask, cap,
                    r.soften, r.overrun, r.tol)
                res.backend = "fused"
                results[i] = res
        obs.counter("round.batch_solves", len(live))
        obs.counter("solver.batch_rows", rows)
        obs.counter("solver.batch_padded_rows", padded)
        obs.annotate(rows=rows, padded_rows=padded, slots=n_slots,
                     chips=devices)
    per = t.elapsed_s / max(len(requests), 1)
    for r in results:
        r.solve_time_s = per
    return results


# ---------------------------------------------------------------------------
# Program 2: the fused temporal round (pricing + masking + solve)
# ---------------------------------------------------------------------------

def _price_temporal(blob, rattrs, *, offsets: tuple, lam_co2: float,
                    lam_h2o: float, defer_eps: float, guard_s: float,
                    lifetime_s: float, embodied_gco2: float,
                    embodied_water_l: float):
    """Traced pricing + masking of the (jobs × slots × regions) grid —
    the device half shared by the fixed-budget and warm-startable temporal
    programs. Returns ``(cost, mask, cap_t, valid)`` flattened to
    ``[Mb, S·R]`` columns."""
    Mb = blob.shape[0]
    S = len(offsets)
    R = rattrs.shape[1]
    E, t = blob[:, 0, None, None], blob[:, 1, None, None]
    budget, valid = blob[:, 2], blob[:, 3] > 0.5
    signals = blob[:, 4:4 + 3 * S * R].reshape(Mb, S, 3 * R)
    latency = blob[:, 4 + 3 * S * R:4 + 3 * S * R + R]
    allowed0 = blob[:, 4 + 3 * S * R + R:]
    ci = signals[..., :R]
    ewif = signals[..., R:2 * R]
    wue = signals[..., 2 * R:]
    pue, wsf, ref_row, cap = rattrs[0], rattrs[1], rattrs[2], rattrs[3]

    co2 = footprint.total_carbon(E, ci, t, lifetime_s, embodied_gco2)
    h2o = footprint.total_water(E, pue[None, None, :], ewif, wue,
                                wsf[None, None, :], t, lifetime_s,
                                embodied_water_l)
    co2_max = jnp.maximum(co2.max(axis=(1, 2)), 1e-9)
    h2o_max = jnp.maximum(h2o.max(axis=(1, 2)), 1e-9)
    obj = (lam_co2 * co2 / co2_max[:, None, None]
           + lam_h2o * h2o / h2o_max[:, None, None])
    obj = obj + ref_row[None, None, :]
    obj = obj + defer_eps * jnp.arange(S)[None, :, None]

    need = jnp.asarray(offsets)[None, :, None] + latency[:, None, :]
    allowed = need + guard_s <= budget[:, None, None] + 1e-9
    allowed = allowed.at[:, 0, :].set(allowed0 > 0.5)

    cost = obj.reshape(Mb, S * R)
    mask = valid[:, None] & allowed.reshape(Mb, S * R)
    cap_t = jnp.tile(cap, S)
    return cost, mask, cap_t, valid


@functools.partial(jax.jit, static_argnames=(
    "offsets", "lam_co2", "lam_h2o", "defer_eps", "guard_s", "lifetime_s",
    "embodied_gco2", "embodied_water_l", "want_plan", "impl", "eps0",
    "eps_min", "iters", "anneal_stages", "interpret"))
def _temporal_program(blob, rattrs, *,
                      offsets: tuple, lam_co2: float, lam_h2o: float,
                      defer_eps: float, guard_s: float, lifetime_s: float,
                      embodied_gco2: float, embodied_water_l: float,
                      want_plan: bool, impl: str,
                      eps0: float = 0.5, eps_min: float = 0.005,
                      iters: int = 60, anneal_stages: int = 6,
                      interpret: bool = False):
    """The whole forecast-driven round on device: Eq 1/5 footprint pricing
    over the (jobs × slots × regions) grid, Eq-7 normalization, the λ-mixed
    Eq-8 objective + per-slot deferral ramp, the Eq-11 deadline/guard
    feasibility mask, and the fused prepare/Sinkhorn/extraction.

    Mirrors ``forecast.planner.build_temporal_plan`` exactly (the parity
    tests pin the decisions); ``core.footprint`` is pure arithmetic, so the
    same Eq 1-6 implementations trace unchanged.

    Packed inputs (host→device copies, not semantics) — everything that
    varies per round rides in TWO arrays, so a round costs two host→device
    copies total:
      blob    [Mb, 4 + 3SR + 2R]  per-job columns:
                [E | exec_t | slack budget | row-validity    (4)
                 | ci, ewif, wue forecast rows, slot-major   (3SR)
                 | latency | slot-0 Eq-11 mask (0/1)         (2R)]
      rattrs  [4, R]              pue | wsf | λ_ref history row | capacity
    Per-pipeline constants are static: compiled straight into the program.
    """
    cost, mask, cap_t, valid = _price_temporal(
        blob, rattrs, offsets=offsets, lam_co2=lam_co2, lam_h2o=lam_h2o,
        defer_eps=defer_eps, guard_s=guard_s, lifetime_s=lifetime_s,
        embodied_gco2=embodied_gco2, embodied_water_l=embodied_water_l)
    Cn, X, scale = _solve_core(cost, mask, cap_t, valid, impl=impl,
                               eps0=eps0, eps_min=eps_min, iters=iters,
                               anneal_stages=anneal_stages,
                               interpret=interpret)
    if want_plan:
        return Cn, X, scale, cost, mask
    return Cn, X, scale


@functools.partial(jax.jit, static_argnames=(
    "offsets", "lam_co2", "lam_h2o", "defer_eps", "guard_s", "lifetime_s",
    "embodied_gco2", "embodied_water_l", "eps0", "eps_min", "iters",
    "anneal_stages"))
def _temporal_adaptive_program(blob, rattrs, g0, tol, *,
                               offsets: tuple, lam_co2: float,
                               lam_h2o: float, defer_eps: float,
                               guard_s: float, lifetime_s: float,
                               embodied_gco2: float, embodied_water_l: float,
                               eps0: float, eps_min: float, iters: int,
                               anneal_stages: int):
    """``_temporal_program`` with the adaptive warm-startable Sinkhorn
    (convergence-exit ``while_loop``, XLA impl only): the caller supplies
    initial column potentials ``g0`` ([S·R], zeros for a cold start) and
    gets back the converged potentials plus the inner-iteration count —
    the live-serving path that carries duals between consecutive rounds
    (``SinkhornWarmStart``)."""
    cost, mask, cap_t, valid = _price_temporal(
        blob, rattrs, offsets=offsets, lam_co2=lam_co2, lam_h2o=lam_h2o,
        defer_eps=defer_eps, guard_s=guard_s, lifetime_s=lifetime_s,
        embodied_gco2=embodied_gco2, embodied_water_l=embodied_water_l)
    C, log_a, log_b, Cn, scale = _prepare_device(cost, mask, cap_t, valid)
    f, g, eps, used = jax_solver._sinkhorn_log_adaptive_impl(
        C, log_a, log_b, g0, tol, eps0=eps0, eps_min=eps_min, iters=iters,
        anneal_stages=anneal_stages)
    X = jnp.exp((f[:, None] + g[None, :] - C) / eps)[:Cn.shape[0]]
    X = X / jnp.maximum(X.sum(axis=1, keepdims=True), 1e-30)
    return Cn, X, scale, g, used


@dataclasses.dataclass
class SinkhornWarmStart:
    """Column-potential carry between consecutive fused temporal rounds.

    The temporal OT's column space — (region, slot) cells — is fixed per
    pipeline while the row space (jobs) changes every round, so the column
    potentials ``g`` are the part of the duals worth carrying: passed as
    the next round's ``g0``, a drifted-telemetry round converges in a
    handful of final-ε iterations instead of the full annealed schedule.
    The first round (or any column-shape change) runs cold: zeros init +
    the full schedule. Cold and warm iteration counts are recorded via
    ``repro.obs`` (``solver.sinkhorn_iters_cold`` / ``_warm``) and kept on
    the object for reporting (``repro.serve`` folds them into the BENCH
    round-latency fields).
    """
    tol: float = jax_solver.SINKHORN_TOL
    g: Optional[np.ndarray] = None
    cold_iters: list = dataclasses.field(default_factory=list)
    warm_iters: list = dataclasses.field(default_factory=list)

    def reset(self) -> None:
        self.g = None

    @property
    def mean_cold_iters(self) -> float:
        return float(np.mean(self.cold_iters)) if self.cold_iters else 0.0

    @property
    def mean_warm_iters(self) -> float:
        return float(np.mean(self.warm_iters)) if self.warm_iters else 0.0


def fused_temporal_round(inst, now_s: float, ci, ewif, wue, pue, wsf,
                         slot_offsets, server, lam_co2: float,
                         lam_h2o: float, lam_ref: float = 0.0,
                         co2_ref=None, h2o_ref=None,
                         defer_eps: float = 1e-3, guard_s: float = 240.0,
                         want_plan: bool = False,
                         sinkhorn_impl: Optional[str] = None,
                         interpret: Optional[bool] = None,
                         eps_min: float = 0.005,
                         warm_start: Optional[SinkhornWarmStart] = None):
    """Price, mask, and solve one forecast round in a single device dispatch.

    Same signature family as ``forecast.planner.build_temporal_plan`` (the
    unfused path), plus the solve. Returns ``(cost, allowed, capacity,
    SolveResult)``. With ``want_plan`` (offline window recording) the raw
    priced tensors leave the device; otherwise the returned cost/allowed
    are re-derived host-side from the normalized costs that come back
    anyway (identical to the priced tensor on every allowed arc; forbidden
    arcs carry ``solvers.BIG``) — no extra device transfer either way.

    ``warm_start`` switches to the adaptive Sinkhorn (convergence-exit
    loop, XLA impl): the object's carried column potentials seed the solve
    — zeros + the full annealed schedule when empty (cold) — and the
    converged potentials plus iteration counts are written back, so
    consecutive calls with the same object warm-start each other
    (the ``repro.serve`` decision loop's between-round carry).
    """
    jobs = inst.jobs
    M, N = inst.shape
    S = len(slot_offsets)
    assert slot_offsets[0] == 0.0 and ci.shape == (M, S, N)
    if co2_ref is not None and h2o_ref is not None:
        ref_row = lam_ref * (lam_co2 * np.asarray(co2_ref)
                             + lam_h2o * np.asarray(h2o_ref))
    else:
        ref_row = np.zeros(N)

    cap = np.asarray(inst.capacity, np.int64)
    bucket, _ = _pad_rows(M)
    # The warm-startable adaptive program runs the XLA loop only; the span
    # names the implementation that actually runs.
    impl = ("xla" if warm_start is not None
            else sinkhorn_impl or sinkhorn_impl_default())
    interp = _interpret(impl, interpret)

    with obs.timed("solver.fused_round", jobs=M, slots=S, regions=N,
                  bucket=bucket, occupancy=(M + 1) / bucket,
                  sinkhorn_iters=jax_solver.SINKHORN_ITERS
                  * jax_solver.SINKHORN_STAGES,
                  eps0=jax_solver.SINKHORN_EPS0, eps_min=eps_min,
                  anneal_stages=jax_solver.SINKHORN_STAGES, impl=impl,
                  interpret=interp) as t:
        # One zero-initialized padded blob, filled in place: padding rows fall
        # out as zero-mass (validity 0) rows and the whole round uploads as two
        # contiguous copies (blob + rattrs).
        with obs.span("solver.pack"):
            W = 4 + 3 * S * N + 2 * N
            blob = np.zeros((bucket - 1, W), np.float32)
            for i, j in enumerate(jobs):
                blob[i, 0] = j.energy_kwh
                blob[i, 1] = j.exec_time_s
                blob[i, 3] = 1.0
            # One shared vectorized slack definition (critical-path aware
            # for workflow tasks) — same expression the planner/pricers
            # mask with.
            blob[:M, 2] = problem.slack_budget(jobs, now_s)
            # slot-major [ci | ewif | wue] per slot — [S, 3R] blocks
            # flattened
            blob[:M, 4:4 + 3 * S * N] = np.concatenate(
                [ci, ewif, wue], axis=2).reshape(M, 3 * S * N)
            blob[:M, 4 + 3 * S * N:4 + 3 * S * N + N] = inst.latency
            blob[:M, 4 + 3 * S * N + N:] = inst.allowed
            rattrs = np.stack([pue, wsf, ref_row, cap]).astype(np.float32)
        statics = dict(
            offsets=tuple(float(o) for o in slot_offsets),
            lam_co2=float(lam_co2), lam_h2o=float(lam_h2o),
            defer_eps=float(defer_eps), guard_s=float(guard_s),
            lifetime_s=float(server.lifetime_s),
            embodied_gco2=float(server.embodied_gco2),
            embodied_water_l=float(server.embodied_water_l))
        if warm_start is not None:
            assert not want_plan, \
                "warm_start and want_plan are mutually exclusive"
            cols = S * N
            cold = warm_start.g is None or warm_start.g.shape != (cols,)
            g0 = (np.zeros(cols, np.float32) if cold
                  else warm_start.g.astype(np.float32))
            # Cold: the full annealed schedule with per-stage early exit.
            # Warm: one final-ε stage from the carried potentials, with the
            # whole fixed budget available as the iteration cap (the cap
            # should never bind when the carry is any good).
            budget = jax_solver.SINKHORN_ITERS * jax_solver.SINKHORN_STAGES
            with obs.span("solver.device"):
                out = jax_solver.fetch(_temporal_adaptive_program(
                    jnp.asarray(blob), jnp.asarray(rattrs), jnp.asarray(g0),
                    jnp.float32(warm_start.tol), **statics,
                    eps0=(float(eps_min) if not cold
                          else jax_solver.SINKHORN_EPS0),
                    eps_min=float(eps_min),
                    iters=budget if not cold else jax_solver.SINKHORN_ITERS,
                    anneal_stages=(1 if not cold
                                   else jax_solver.SINKHORN_STAGES)))
            warm_start.g = np.asarray(out[3], np.float32)
            used = int(out[4])
            (warm_start.cold_iters if cold
             else warm_start.warm_iters).append(used)
            obs.observe("solver.sinkhorn_iters_cold" if cold
                        else "solver.sinkhorn_iters_warm", float(used))
            t.set(warm=not cold, adaptive_iters=used)
        else:
            with obs.span("solver.device"):
                out = jax_solver.fetch(_temporal_program(
                    jnp.asarray(blob), jnp.asarray(rattrs), **statics,
                    want_plan=bool(want_plan), impl=impl,
                    eps_min=float(eps_min), interpret=interp))
        _count_sinkhorn(impl, interp)
        Cn = np.asarray(out[0][:M], np.float64)
        X = np.asarray(out[1][:M], np.float64)
        scale = float(out[2])
        mask = Cn < BIG * 0.5          # forbidden arcs are exactly BIG
        # De-normalized costs price the objective; identical to the priced
        # tensor on every allowed arc (forbidden arcs never enter objectives).
        c_eff = np.where(mask, Cn * scale, solvers.BIG)
        cap_t = np.tile(cap, S)

        if int(cap_t.sum()) < M or not mask.any(axis=1).all():
            res = _infeasible(M)
        else:
            res = jax_solver._finalize(X, Cn, c_eff, mask, cap_t,
                                       False, None, None)
            res.backend = "fused"
        t.set(status=res.status)
    res.solve_time_s = t.elapsed_s
    if want_plan:
        cost = np.asarray(out[3][:M], np.float64)
        allowed = np.asarray(out[4][:M], bool)
        return cost, allowed, cap_t, res
    return c_eff, mask, cap_t, res
