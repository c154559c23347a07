"""JAX-native entropic-OT solver — the beyond-paper, TPU-idiomatic backend.

The paper solves Eq 8-11 with CBC branch-and-cut on a CPU head node. On a TPU
fleet the natural formulation is entropic-regularized optimal transport over
the same transportation polytope:

    min ⟨C, X⟩ − ε·H(X)   s.t.  X·1 = a,  Xᵀ·1 = b

with forbidden arcs priced at +BIG. Capacity inequalities become equalities
by appending one dummy supply row (supply = Σcap − M, zero cost) — the
classic balanced-OT reduction. Log-domain Sinkhorn iterations with
ε-annealing drive X toward a vertex of the polytope; as ε→0 the entropic
optimum converges to the LP optimum, which is integral (total unimodularity).
A final greedy confidence rounding + min-cost repair produces the integral
assignment; the integrality gap vs the exact ``flow``/``scipy`` backends is
measured in tests (typically 0 on non-degenerate instances).

Why this exists: the Sinkhorn inner loop is two batched row/col logsumexp
reductions — MXU/VPU-friendly, jittable, vmappable over scheduling windows,
and served by the Pallas kernel in ``repro/kernels/sinkhorn`` on TPU. This is
the TPU-native equivalent of the paper's branch-and-cut (DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import solvers

obs.watch_jax()

BIG = 1e4          # forbidden-arc cost after normalization to ~unit scale
_NEG = -1e9        # log-domain mask value / zero-mass row marginal

# Row-count buckets: cost matrices are padded up to the next bucket (with
# zero-mass rows) before hitting the jitted Sinkhorn, so a whole simulation
# run — thousands of scheduling rounds with jittery window sizes — compiles
# the solver once per bucket instead of once per distinct M. Extends through
# 16384 so the 1M-jobs/day storm regime (multi-thousand-row admission
# windows) stays on tabled buckets instead of the ad-hoc overflow path.
BUCKETS = (4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384)

# The annealed-Sinkhorn schedule baked into ``sinkhorn_log``'s defaults;
# solver spans annotate these so traces record the effective iteration
# budget (iters × anneal_stages) per solve.
SINKHORN_EPS0 = 0.5
SINKHORN_ITERS = 60
SINKHORN_STAGES = 6


# Ad-hoc overflow bucket sizes already warned about: the overflow warning
# fires once per *size*, not once per solve — a storm that overflows into
# bucket 32768 ten thousand times is one actionable signal, not ten
# thousand identical RuntimeWarnings.
_OVERFLOW_WARNED: set = set()


def bucket_for(rows: int) -> int:
    """Smallest bucket ≥ rows (next power of two beyond the table)."""
    for b in BUCKETS:
        if rows <= b:
            return b
    b = BUCKETS[-1]
    while b < rows:
        b *= 2
    if b not in _OVERFLOW_WARNED:
        _OVERFLOW_WARNED.add(b)
        obs.warn("solver.bucket_overflow",
                 f"instance with {rows} rows exceeds the largest padded "
                 f"bucket {BUCKETS[-1]}; falling back to ad-hoc bucket {b} "
                 f"(fresh JIT compile per new size)")
    return b


def _sinkhorn_log_impl(C: jnp.ndarray, log_a: jnp.ndarray, log_b: jnp.ndarray,
                       eps0: float = 0.5, eps_min: float = 0.01,
                       iters: int = 60, anneal_stages: int = 6):
    """Log-stabilized Sinkhorn with geometric ε-annealing.

    Args:
      C: [M, N] cost (forbidden arcs already priced at BIG).
      log_a: [M] log row marginals; log_b: [N] log col marginals. Rows with
        log_a ≈ _NEG carry no mass — padding rows are exact no-ops.
    Returns:
      (f, g, eps): dual potentials and the final ε. The primal plan is
      X = exp((f[:,None] + g[None,:] − C) / ε).
    """
    def col_update(f, eps):
        # g_j = ε·(log b_j − logsumexp_i (f_i − C_ij)/ε)
        return eps * (log_b - jax.nn.logsumexp(
            (f[:, None] - C) / eps, axis=0))

    def row_update(g, eps):
        return eps * (log_a - jax.nn.logsumexp(
            (g[None, :] - C) / eps, axis=1))

    def stage(carry, eps):
        f, g = carry

        def body(_, fg):
            f, g = fg
            g = col_update(f, eps)
            f = row_update(g, eps)
            return (f, g)

        f, g = jax.lax.fori_loop(0, iters, body, (f, g))
        return (f, g), None

    decay = (eps_min / eps0) ** (1.0 / max(anneal_stages - 1, 1))
    eps_sched = eps0 * decay ** jnp.arange(anneal_stages)
    f0 = jnp.zeros_like(log_a)
    g0 = jnp.zeros_like(log_b)
    (f, g), _ = jax.lax.scan(stage, (f0, g0), eps_sched)
    return f, g, eps_sched[-1]


# Convergence tolerance of the adaptive (warm-startable) Sinkhorn: a stage
# exits once the sup-norm change of the column potentials per iteration
# drops below this. Small enough that the rounded assignment matches the
# fixed-budget schedule; reached in a handful of iterations from a warm
# start (see ``repro.core.round.SinkhornWarmStart``).
SINKHORN_TOL = 1e-5


def _sinkhorn_log_adaptive_impl(C: jnp.ndarray, log_a: jnp.ndarray,
                                log_b: jnp.ndarray, g0: jnp.ndarray,
                                tol: jnp.ndarray, eps0: float = 0.5,
                                eps_min: float = 0.01, iters: int = 60,
                                anneal_stages: int = 6):
    """Warm-startable annealed Sinkhorn with per-stage convergence exit.

    Same fixed point as ``_sinkhorn_log_impl`` (Sinkhorn at fixed ε has a
    unique fixed point up to a constant shift, which cancels in the primal
    plan), but (a) iterations start from caller-supplied column potentials
    ``g0`` — the update order is (f ← row, g ← col) so a warm ``g0`` is
    honored instead of being overwritten — and (b) each annealing stage
    exits as soon as the per-iteration sup-norm change of ``g`` drops
    below ``tol``, with the total inner-iteration count reported.

    A *cold* call passes ``g0 = 0`` and the full annealing schedule; a
    *warm* call passes the previous round's converged potentials with
    ``anneal_stages=1, eps0=eps_min`` — near a drifted optimum, the single
    final-ε stage converges in a handful of iterations where the cold
    schedule spends hundreds (recorded via ``repro.obs`` in
    ``repro.core.round``).

    Returns ``(f, g, eps, iters_used)``.
    """
    def col_update(f, eps):
        return eps * (log_b - jax.nn.logsumexp(
            (f[:, None] - C) / eps, axis=0))

    def row_update(g, eps):
        return eps * (log_a - jax.nn.logsumexp(
            (g[None, :] - C) / eps, axis=1))

    def stage(carry, eps):
        f, g, total = carry

        def cond(state):
            _, _, k, delta = state
            return jnp.logical_and(k < iters, delta > tol)

        def body(state):
            _, g, k, _ = state
            f = row_update(g, eps)
            g_new = col_update(f, eps)
            delta = jnp.max(jnp.abs(g_new - g))
            return (f, g_new, k + 1, delta)

        f, g, k, _ = jax.lax.while_loop(
            cond, body, (f, g, jnp.int32(0), jnp.float32(jnp.inf)))
        return (f, g, total + k), None

    decay = (eps_min / eps0) ** (1.0 / max(anneal_stages - 1, 1))
    eps_sched = eps0 * decay ** jnp.arange(anneal_stages)
    f0 = jnp.zeros_like(log_a)
    (f, g, used), _ = jax.lax.scan(stage, (f0, g0, jnp.int32(0)), eps_sched)
    return f, g, eps_sched[-1], used


sinkhorn_log_adaptive = functools.partial(jax.jit, static_argnames=(
    "iters", "anneal_stages"))(_sinkhorn_log_adaptive_impl)


# Single-instance and window-batched entry points. The batched variant vmaps
# over a stack of same-bucket instances (queued scheduling windows solved in
# one device dispatch); both share one implementation and therefore one
# compile cache keyed on (bucket, N, iters, stages).
sinkhorn_log = functools.partial(jax.jit, static_argnames=(
    "iters", "anneal_stages"))(_sinkhorn_log_impl)


def _sinkhorn_batched_impl(C, log_a, log_b, eps0: float = 0.5,
                           eps_min: float = 0.01, iters: int = 60,
                           anneal_stages: int = 6):
    def one(c, la, lb):
        return _sinkhorn_log_impl(c, la, lb, eps0, eps_min, iters,
                                  anneal_stages)
    return jax.vmap(one)(C, log_a, log_b)


sinkhorn_log_batched = functools.partial(jax.jit, static_argnames=(
    "iters", "anneal_stages"))(_sinkhorn_batched_impl)


@jax.jit
def plan_from_duals(C, f, g, eps):
    return jnp.exp((f[:, None] + g[None, :] - C) / eps)


def fetch(out):
    """``jax.device_get`` of a solve's device outputs that adds the bytes
    it copies to the host to counter ``solver.d2h_bytes``."""
    out = jax.device_get(out)
    obs.counter("solver.d2h_bytes", sum(
        np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(out)))
    return out


def _round_to_vertex(X: np.ndarray, cost: np.ndarray, mask: np.ndarray,
                     capacity: np.ndarray) -> np.ndarray:
    """Greedy confidence rounding + cheapest-feasible repair.

    Jobs are committed in decreasing order of plan confidence (max row prob);
    each takes its argmax column if capacity remains, else its cheapest
    allowed column with spare capacity.
    """
    M, N = cost.shape
    assign = np.full(M, -1, dtype=np.int64)
    left = capacity.astype(np.int64).copy()
    Xm = np.where(mask, X, -np.inf)
    conf = Xm.max(axis=1)
    for m in np.argsort(-conf):
        if not mask[m].any():
            continue
        prefs = np.argsort(np.where(mask[m], cost[m] - 2.0 * BIG * Xm[m],
                                    np.inf))
        for n in prefs:
            if mask[m, n] and left[n] > 0:
                assign[m] = n
                left[n] -= 1
                break
    return assign


def _improve_2swap(assign: np.ndarray, cost: np.ndarray, mask: np.ndarray,
                   capacity: np.ndarray, rounds: int = 3) -> np.ndarray:
    """Local search: single-job moves + pairwise swaps until no improvement.

    Polishes the rounded vertex; with the Sinkhorn duals already near-optimal
    this usually closes the (small) remaining gap to the exact optimum.
    Each pass makes every improving single move in row order, then the best
    improving swap; both cost O(M·N), plus O(_SCAN_BLOCK·N) per move.
    """
    N = cost.shape[1]
    used = np.bincount(assign[assign >= 0], minlength=N)
    passes = moves = swaps = 0
    for _ in range(rounds):
        passes += 1
        moved = _move_pass(assign, _gains(assign, cost, mask), used, capacity)
        moves += moved
        m1, m2, delta = _best_swap(assign, _gains(assign, cost, mask))
        if delta < -1e-12:
            assign[m1], assign[m2] = assign[m2], assign[m1]
            swaps += 1
        elif not moved:
            break
    obs.annotate(passes=passes, moves=moves, swaps=swaps)
    return assign


# Rows the single-move pass prices at once. A move that opens or fills a
# column restarts the scan after the mover and discards the rest of its block.
_SCAN_BLOCK = 256


def _gains(assign, cost, mask):
    """``G[m, q] = cost[m, q] - cost[m, assign[m]]``, the change in cost of
    moving row m to column q; ``inf`` where the arc is masked, at the row's
    own column and on unassigned rows."""
    rows = np.arange(len(assign))
    ok = assign >= 0
    cur = np.where(ok, assign, 0)
    G = np.where(mask & ok[:, None], cost - cost[rows, cur][:, None], np.inf)
    G[rows, cur] = np.inf
    return G


def _move_pass(assign, G, used, capacity) -> int:
    """In row order, move each row to its cheapest column with spare
    capacity where that lowers the cost (the lowest column on ties),
    updating ``assign`` and ``used``. The open columns change only at a move,
    so rows are priced against them a block at a time; a move that opens or
    fills a column restarts the scan after the mover. Returns the number of
    moves."""
    M = len(assign)
    moves = m = 0
    while m < M:
        open_ = used < capacity
        D = np.where(open_, G[m:m + _SCAN_BLOCK], np.inf)
        n = D.argmin(axis=1)
        start, m = m, m + len(n)
        for h in np.flatnonzero(D[np.arange(len(n)), n] < -1e-12):
            r, q = start + h, n[h]
            used[assign[r]] -= 1
            used[q] += 1
            assign[r] = q
            moves += 1
            if not np.array_equal(used < capacity, open_):
                m = r + 1
                break
    return moves


def _best_swap(assign, G):
    """The swap of two rows in different columns that lowers the cost most:
    ``(m1, m2, delta)``. Swapping m1 (column p) with m2 (column q) changes
    the cost by ``G[m1, q] + G[m2, p]``, so each column pair's best swap
    pairs the row of p that gains most in q with the row of q that gains
    most in p (the lowest row on ties); ``delta`` is ``inf`` where no swap
    is allowed."""
    N = G.shape[1]
    best = np.full((N, N), np.inf)
    arg = np.zeros((N, N), np.int64)
    rows = np.flatnonzero(assign >= 0)
    if rows.size:
        # Rows grouped by column, ascending within each group.
        order = rows[np.argsort(assign[rows], kind="stable")]
        cols, starts = np.unique(assign[order], return_index=True)
        Gs = G[order]
        low = np.minimum.reduceat(Gs, starts, axis=0)
        size = np.diff(np.append(starts, len(order)))
        at = np.where(Gs == np.repeat(low, size, axis=0), order[:, None],
                      len(assign))
        best[cols] = low
        arg[cols] = np.minimum.reduceat(at, starts, axis=0)
    delta = best + best.T
    np.fill_diagonal(delta, np.inf)
    p, q = np.unravel_index(np.argmin(delta), delta.shape)
    return arg[p, q], arg[q, p], delta[p, q]


def _effective(cost, allowed, soften, overrun, tol, sigma):
    if soften:
        assert overrun is not None and tol is not None
        c_eff = solvers.soft_cost(cost, allowed, overrun, tol, sigma)
        mask = np.ones_like(allowed, dtype=bool)
    else:
        c_eff = cost.astype(np.float64)
        mask = allowed.astype(bool)
    return c_eff, mask


def _infeasible(M):
    return solvers.SolveResult(assign=np.full(M, -1), objective=float("inf"),
                               status="infeasible", solve_time_s=0.0,
                               penalties=np.zeros(M), backend="jax")


def _prepare(c_eff, mask, cap, pad_rows: int):
    """Padded OT inputs: [M real rows | dummy slack row | pad_rows zero-mass
    rows]. Zero-mass rows (log marginal = _NEG) are exact no-ops in the
    log-domain updates, so padding changes nothing but the compiled shape."""
    M, N = c_eff.shape
    # Normalize costs to ~unit scale so ε has a universal meaning.
    scale = max(float(np.abs(c_eff[mask]).max()), 1e-9)
    Cn = np.where(mask, c_eff / scale, BIG)
    slack = int(cap.sum()) - M
    # Dummy row absorbs spare capacity (zero cost everywhere).
    C = np.vstack([Cn, np.zeros((1 + pad_rows, N))]).astype(np.float32)
    a = np.concatenate([np.ones(M), [max(slack, 1e-9)]])
    total = a.sum()
    log_a = np.concatenate([np.log(a / total),
                            np.full(pad_rows, _NEG)]).astype(np.float32)
    log_b = np.log(np.maximum(cap.astype(np.float64), 1e-12)
                   / total).astype(np.float32)
    return C, log_a, log_b, Cn


def _finalize(X, Cn, c_eff, mask, cap, soften, overrun, tol):
    """Round the (real-row) plan to an integral vertex + polish + price.
    Span ``solver.finalize``, with children ``solver.round_vertex``,
    ``solver.ssp_repair`` (only when taken) and ``solver.polish``."""
    with obs.span("solver.finalize", jobs=Cn.shape[0]):
        M = Cn.shape[0]
        X = X / np.maximum(X.sum(axis=1, keepdims=True), 1e-30)
        plan_obj = float(np.where(mask, X * c_eff, 0.0).sum())
        with obs.span("solver.round_vertex"):
            assign = _round_to_vertex(X, Cn, mask, cap)
        if (assign < 0).any():
            # Greedy rounding stranded a job (capacity-tight instance):
            # repair with the exact successive-shortest-path solver on the
            # same normalized costs. Only genuinely infeasible instances
            # survive this.
            from repro.core.solvers import flow_solver
            obs.counter("solver.ssp_repair")
            with obs.span("solver.ssp_repair"):
                assign = flow_solver._ssp_assign(Cn, mask, cap)
        if (assign >= 0).all():
            with obs.span("solver.polish"):
                assign = _improve_2swap(assign, Cn, mask, cap)
        penalties = np.zeros(M)
        if (assign < 0).any():
            return solvers.SolveResult(assign=assign, objective=float("inf"),
                                       status="infeasible", solve_time_s=0.0,
                                       penalties=penalties, backend="jax")
        obj = float(c_eff[np.arange(M), assign].sum())
        if soften:
            excess = np.maximum(overrun - tol[:, None], 0.0)
            penalties = excess[np.arange(M), assign]
        return solvers.SolveResult(assign=assign, objective=obj,
                                   status="rounded", solve_time_s=0.0,
                                   penalties=penalties, backend="jax",
                                   plan_objective=plan_obj)


@solvers.register("jax", on_device=True)
def solve(cost: np.ndarray, allowed: np.ndarray, capacity: np.ndarray, *,
          soften: bool = False, overrun: Optional[np.ndarray] = None,
          tol: Optional[np.ndarray] = None, sigma: float = 10.0,
          eps_min: float = 0.005,
          pad_to_bucket: bool = True) -> solvers.SolveResult:
    def run() -> solvers.SolveResult:
        M, N = cost.shape
        c_eff, mask = _effective(cost, allowed, soften, overrun, tol, sigma)
        cap = capacity.astype(np.int64)
        if int(cap.sum()) < M or not mask.any(axis=1).all():
            return _infeasible(M)
        rows = M + 1
        pad = (bucket_for(rows) - rows) if pad_to_bucket else 0
        C, log_a, log_b, Cn = _prepare(c_eff, mask, cap, pad)
        f, g, eps = sinkhorn_log(jnp.asarray(C), jnp.asarray(log_a),
                                 jnp.asarray(log_b), eps_min=eps_min)
        X = fetch(plan_from_duals(jnp.asarray(C), f, g, eps))[:M]
        if obs.enabled():
            # row-marginal residual: each real row targets mass 1/Σcap
            total = max(float(cap.sum()), 1e-9)
            residual = float(np.abs(X.sum(axis=1) * total - 1.0).max())
            obs.annotate(bucket=rows + pad, pad=pad,
                         occupancy=rows / (rows + pad),
                         sinkhorn_iters=SINKHORN_ITERS * SINKHORN_STAGES,
                         eps0=SINKHORN_EPS0, eps_min=eps_min,
                         anneal_stages=SINKHORN_STAGES, residual=residual)
        return _finalize(X, Cn, c_eff, mask, cap, soften, overrun, tol)
    return solvers._timed(run)


def solve_many(costs, alloweds, capacities, *, soften: bool = False,
               overruns=None, tols=None, sigma: float = 10.0,
               eps_min: float = 0.005):
    """Batched entry point: solve K instances, vmapping the Sinkhorn loop
    over groups of same-bucket instances.

    Queued scheduling windows (a scenario sweep's backlog, a replayed
    multi-round trace, a Monte-Carlo ensemble) usually have jittery row
    counts; bucketing pads them to a handful of compiled shapes and each
    group runs as ONE device dispatch. Returns a list of SolveResults in
    input order.
    """
    K = len(costs)
    overruns = overruns if overruns is not None else [None] * K
    tols = tols if tols is not None else [None] * K
    results: list = [None] * K
    groups: dict = {}
    with obs.timed("solver.solve_many", K=K) as t:
        for k in range(K):
            cost = np.asarray(costs[k], np.float64)
            allowed = np.asarray(alloweds[k], bool)
            cap = np.asarray(capacities[k]).astype(np.int64)
            M, N = cost.shape
            c_eff, mask = _effective(cost, allowed, soften, overruns[k],
                                     tols[k], sigma)
            if int(cap.sum()) < M or not mask.any(axis=1).all():
                results[k] = _infeasible(M)
                continue
            rows = M + 1
            pad = bucket_for(rows) - rows
            C, log_a, log_b, Cn = _prepare(c_eff, mask, cap, pad)
            groups.setdefault((bucket_for(rows), N), []).append(
                (k, C, log_a, log_b, Cn, c_eff, mask, cap))
        for (_, _), items in groups.items():
            Cb = jnp.asarray(np.stack([it[1] for it in items]))
            la = jnp.asarray(np.stack([it[2] for it in items]))
            lb = jnp.asarray(np.stack([it[3] for it in items]))
            fb, gb, eps = sinkhorn_log_batched(Cb, la, lb, eps_min=eps_min)
            plans = fetch(jnp.exp(
                (fb[:, :, None] + gb[:, None, :] - Cb) / eps[:, None, None]))
            for it, X in zip(items, plans):
                k, _, _, _, Cn, c_eff, mask, cap = it
                M = Cn.shape[0]
                results[k] = _finalize(X[:M], Cn, c_eff, mask, cap, soften,
                                       overruns[k], tols[k])
        t.set(buckets=len(groups),
              sinkhorn_iters=SINKHORN_ITERS * SINKHORN_STAGES)
    per = t.elapsed_s / max(K, 1)
    for r in results:
        r.solve_time_s = per
    return results
