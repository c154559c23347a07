"""Solver cross-validation: exactness of ``flow``, the soft-cost fold, the
Sinkhorn backend's integrality gap (paper Eqs 8-13), and the host polish
against its quadratic formulation."""
import numpy as np
import pytest
import scipy.optimize as sopt
import scipy.sparse as sp
from hypothesis import given, settings, strategies as st

from repro.core import solvers
from repro.core.solvers import jax_solver


def _random_instance(rng, M=None, N=None, feasible=True):
    M = M or int(rng.integers(3, 40))
    N = N or int(rng.integers(2, 6))
    cost = rng.random((M, N)) * 10
    allowed = rng.random((M, N)) < 0.8
    if feasible:
        forced = rng.integers(0, N, M)
        allowed[np.arange(M), forced] = True
    cap = rng.integers(1, max(M // max(N - 1, 1), 2), N)
    while feasible and cap.sum() < M:
        cap[rng.integers(0, N)] += 1
    if feasible:
        # Every row fits its forced column: a feasible assignment exists
        # (enough capacity in total is not enough when rows have one arc).
        cap = np.maximum(cap, np.bincount(forced, minlength=N))
    return cost, allowed, cap


@pytest.mark.parametrize("seed", range(10))
def test_flow_matches_scipy_exactly(seed):
    rng = np.random.default_rng(seed)
    cost, allowed, cap = _random_instance(rng)
    r_ref = solvers.solve(cost, allowed, cap, backend="scipy")
    r_flow = solvers.solve(cost, allowed, cap, backend="flow")
    assert r_ref.status == "optimal"
    assert r_flow.status == "optimal"
    assert np.isclose(r_flow.objective, r_ref.objective, atol=1e-8)


@pytest.mark.parametrize("seed", range(5))
def test_jax_sinkhorn_gap_small(seed):
    rng = np.random.default_rng(100 + seed)
    cost, allowed, cap = _random_instance(rng)
    r_ref = solvers.solve(cost, allowed, cap, backend="scipy")
    r_jax = solvers.solve(cost, allowed, cap, backend="jax")
    assert r_jax.feasible
    gap = (r_jax.objective - r_ref.objective) / max(abs(r_ref.objective),
                                                    1e-9)
    assert gap <= 0.02, f"integrality gap {gap:.2%}"
    # capacity respected
    counts = np.bincount(r_jax.assign, minlength=len(cap))
    assert (counts <= cap).all()


def _literal_soft_milp(cost, allowed, capacity, overrun, tol, sigma):
    """Eqs 12-13 with EXPLICIT penalty variables P[m,n] (the literal paper
    formulation) via scipy.milp — proves the folded-cost reduction exact."""
    M, N = cost.shape
    nx = M * N
    # variables: x (binary, M*N) then p (continuous >= 0, M*N)
    c = np.concatenate([cost.reshape(-1), sigma * np.ones(nx)])
    rows, cols, vals, lb, ub = [], [], [], [], []
    r = 0
    for m in range(M):                       # assignment == 1
        for n in range(N):
            rows.append(r); cols.append(m * N + n); vals.append(1.0)
        lb.append(1.0); ub.append(1.0); r += 1
    for n in range(N):                       # capacity
        for m in range(M):
            rows.append(r); cols.append(m * N + n); vals.append(1.0)
        lb.append(0.0); ub.append(float(capacity[n])); r += 1
    for m in range(M):                       # Eq 13 per job
        for n in range(N):
            rows.append(r); cols.append(m * N + n)
            vals.append(float(overrun[m, n]))
            rows.append(r); cols.append(nx + m * N + n); vals.append(-1.0)
        lb.append(-np.inf); ub.append(float(tol[m])); r += 1
    A = sp.csr_matrix((vals, (rows, cols)), shape=(r, 2 * nx))
    res = sopt.milp(
        c=c, constraints=sopt.LinearConstraint(A, lb, ub),
        integrality=np.concatenate([np.ones(nx), np.zeros(nx)]),
        bounds=sopt.Bounds(np.zeros(2 * nx),
                           np.concatenate([np.ones(nx),
                                           np.full(nx, np.inf)])))
    assert res.success
    return res.fun


@pytest.mark.parametrize("seed", range(5))
def test_soft_fold_equals_literal_formulation(seed):
    """The folded per-arc penalty (solvers.soft_cost) is exactly the
    literal Eq 12-13 MILP optimum."""
    rng = np.random.default_rng(200 + seed)
    M, N = int(rng.integers(3, 10)), int(rng.integers(2, 5))
    cost = rng.random((M, N))
    overrun = rng.random((M, N)) * 2
    tol = rng.random(M)
    allowed = overrun <= tol[:, None]
    cap = np.full(N, M)
    sigma = 3.0
    folded = solvers.solve(cost, allowed, cap, backend="flow", soften=True,
                           overrun=overrun, tol=tol, sigma=sigma)
    literal = _literal_soft_milp(cost, allowed, cap, overrun, tol, sigma)
    assert np.isclose(folded.objective, literal, atol=1e-7)


def test_infeasible_detection():
    cost = np.ones((3, 2))
    allowed = np.zeros((3, 2), bool)
    cap = np.array([1, 1])
    for backend in ("scipy", "flow", "jax"):
        r = solvers.solve(cost, allowed, cap, backend=backend)
        assert not r.feasible


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_flow_optimality_property(seed):
    """Property: flow's assignment is feasible and its objective matches the
    exact LP/MILP optimum on every random instance."""
    rng = np.random.default_rng(seed)
    cost, allowed, cap = _random_instance(rng, M=int(rng.integers(3, 25)))
    r_flow = solvers.solve(cost, allowed, cap, backend="flow")
    r_ref = solvers.solve(cost, allowed, cap, backend="scipy")
    assert r_flow.status == r_ref.status == "optimal"
    counts = np.bincount(r_flow.assign, minlength=len(cap))
    assert (counts <= cap).all()
    assert all(allowed[m, r_flow.assign[m]] for m in range(cost.shape[0]))
    assert np.isclose(r_flow.objective, r_ref.objective, atol=1e-8)


def _quadratic_2swap(assign, cost, mask, capacity, rounds=3):
    """The polish as first written: a Python loop over rows for the single
    moves and a dense M x M delta matrix for the swap. Returns the
    assignment and ``(passes, moves, swaps)``."""
    M, N = cost.shape
    used = np.bincount(assign[assign >= 0], minlength=N)
    passes = moves = swaps = 0
    for _ in range(rounds):
        passes += 1
        improved = False
        for m in range(M):
            if assign[m] < 0:
                continue
            cur = assign[m]
            deltas = np.where(mask[m] & (used < capacity),
                              cost[m] - cost[m, cur], np.inf)
            deltas[cur] = np.inf
            n = int(np.argmin(deltas))
            if deltas[n] < -1e-12:
                used[cur] -= 1
                used[n] += 1
                assign[m] = n
                moves += 1
                improved = True
        a = assign
        ok = a >= 0
        cm = cost[np.arange(M), np.where(ok, a, 0)]
        c_m1_a2 = np.where(mask[:, a] & ok[None, :], cost[:, a], np.inf)
        delta = c_m1_a2 + c_m1_a2.T - cm[:, None] - cm[None, :]
        delta[~ok] = np.inf
        delta[:, ~ok] = np.inf
        np.fill_diagonal(delta, np.inf)
        m1, m2 = np.unravel_index(np.argmin(delta), delta.shape)
        if delta[m1, m2] < -1e-12:
            assign[m1], assign[m2] = assign[m2], assign[m1]
            swaps += 1
            improved = True
        if not improved:
            break
    return assign, (passes, moves, swaps)


def _polish(monkeypatch, assign, cost, mask, capacity, rounds=3):
    """``jax_solver._improve_2swap`` on a copy of ``assign``, with the
    ``(passes, moves, swaps)`` it annotates."""
    seen = {}
    monkeypatch.setattr(jax_solver.obs, "annotate", seen.update)
    out = jax_solver._improve_2swap(assign.copy(), cost, mask, capacity,
                                    rounds)
    return out, (seen["passes"], seen["moves"], seen["swaps"])


def _polish_instance(rng, M, N, soft, tight):
    """A feasible rounded assignment to polish: each row on a random allowed
    column, every column with spare capacity; ``soft`` allows every arc (the
    Eqs 12-13 rounds); ``tight`` makes column 0 cheap with 2 spare slots,
    the shape of a cheap region beside the defer arc."""
    cost = rng.random((M, N)) * 10
    mask = np.ones((M, N), bool) if soft else rng.random((M, N)) < 0.6
    assign = rng.integers(0, N, M)
    mask[np.arange(M), assign] = True
    used = np.bincount(assign, minlength=N)
    capacity = used + rng.integers(0, max(M // N, 2), N)
    if tight:
        cost[:, 0] -= 5.0
        capacity[0] = used[0] + 2
    return assign, cost, mask, capacity


def _assert_feasible(assign, mask, capacity):
    assert (assign >= 0).all()
    assert mask[np.arange(len(assign)), assign].all()
    assert (np.bincount(assign, minlength=len(capacity)) <= capacity).all()


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("tight", [False, True], ids=["loose", "tight"])
@pytest.mark.parametrize("soft", [False, True], ids=["masked", "soft"])
@pytest.mark.parametrize("N", [2, 6, 40])
def test_polish_matches_quadratic_reference(monkeypatch, N, soft, tight,
                                            seed):
    """The O(M·N) polish returns the quadratic one's assignment, passes,
    moves and swaps (random costs: no two swaps tie), and stays within
    capacity and mask."""
    rng = np.random.default_rng(1000 * N + 10 * soft + 2 * tight + seed)
    M = int(rng.integers(N + 1, 600))
    assign, cost, mask, capacity = _polish_instance(rng, M, N, soft, tight)
    ref, ref_counts = _quadratic_2swap(assign.copy(), cost, mask, capacity)
    out, counts = _polish(monkeypatch, assign, cost, mask, capacity)
    np.testing.assert_array_equal(out, ref)
    assert counts == ref_counts
    _assert_feasible(out, mask, capacity)


@pytest.mark.parametrize("block", [1, 7, 10 ** 6])
def test_polish_does_not_depend_on_scan_block(monkeypatch, block):
    """The single-move scan's block size changes only how rows are priced,
    never which rows move, where or in what order."""
    monkeypatch.setattr(jax_solver, "_SCAN_BLOCK", block)
    for seed, (N, soft, tight) in enumerate([(6, False, True),
                                             (40, True, False),
                                             (2, False, False)]):
        rng = np.random.default_rng(77 + seed)
        assign, cost, mask, capacity = _polish_instance(rng, 500, N, soft,
                                                        tight)
        ref, ref_counts = _quadratic_2swap(assign.copy(), cost, mask,
                                           capacity)
        out, counts = _polish(monkeypatch, assign, cost, mask, capacity)
        np.testing.assert_array_equal(out, ref)
        assert counts == ref_counts
        _assert_feasible(out, mask, capacity)


def test_polish_pinned_cheap_column_nearly_full(monkeypatch):
    """3,000 rows, 6 columns: 2,500 rows on the defer-like last column, the
    rest on their cheapest other column but 0; every row is cheaper on
    column 0, which has 9 spare slots, and the other regions are full.
    Capacity blocks all but 9 of the would-be moves, so the first pass
    moves at most 9 rows, and the whole polish agrees with the quadratic
    one."""
    rng = np.random.default_rng(31)
    M, N = 3000, 6
    cost = rng.random((M, N)) + np.array([0.0, 1.0, 1.0, 1.0, 1.0, 0.5])
    mask = rng.random((M, N)) < 0.9
    mask[:, [0, N - 1]] = True
    cheapest = 1 + np.where(mask[:, 1:], cost[:, 1:], np.inf).argmin(axis=1)
    assign = np.where(np.arange(M) < 2500, N - 1, cheapest)
    used = np.bincount(assign, minlength=N)
    capacity = used + np.array([9, 0, 0, 0, 0, 500])
    _, first = _polish(monkeypatch, assign, cost, mask, capacity, rounds=1)
    assert first[1] <= 9
    ref, ref_counts = _quadratic_2swap(assign.copy(), cost, mask, capacity)
    out, counts = _polish(monkeypatch, assign, cost, mask, capacity)
    np.testing.assert_array_equal(out, ref)
    assert counts == ref_counts
    _assert_feasible(out, mask, capacity)
