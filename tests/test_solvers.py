"""Solver cross-validation: exactness of ``flow``, the soft-cost fold, and
the Sinkhorn backend's integrality gap (paper Eqs 8-13)."""
import numpy as np
import pytest
import scipy.optimize as sopt
import scipy.sparse as sp
from hypothesis import given, settings, strategies as st

from repro.core import solvers


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
