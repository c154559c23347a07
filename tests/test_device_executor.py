"""Device-parallel cell execution: ``fused_round_batch`` ≡ ``fused_solve``
bit-parity, cell-group batching invariants (property-tested), the
``device`` executor backend ≡ ``serial`` on a pinned plan, extended solver
row buckets (>4096 rows), and the safe XLA host-platform flag helper."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import experiments
from repro.core import round as fused_round
from repro.core.round import SolveRequest, fused_round_batch, group_requests
from repro.core.solvers import jax_solver
from repro.core.solvers.jax_solver import BUCKETS, bucket_for
from repro.launch import devices as launch_devices


def _request(rng, M=12, C=4, soften=False, dtype=np.float64):
    cost = rng.uniform(1.0, 5.0, (M, C)).astype(dtype)
    allowed = rng.random((M, C)) > 0.2
    allowed[:, 0] = True                     # every job has an arc
    return SolveRequest(
        cost=cost, allowed=allowed, capacity=np.full(C, M, np.int64),
        soften=soften, overrun=rng.uniform(0.0, 2.0, (M, C)),
        tol=rng.uniform(0.0, 1.0, M), sigma=8.0)


def _assert_same_result(a, b):
    assert a.status == b.status
    assert a.objective == b.objective        # bit-identical, not approx
    np.testing.assert_array_equal(a.assign, b.assign)
    np.testing.assert_array_equal(a.penalties, b.penalties)


# ---------------------------------------------------------------------------
# fused_round_batch ≡ fused_solve (the tentpole's bit-parity contract)
# ---------------------------------------------------------------------------

def test_batch_matches_single_cell_fused_solve_bitwise():
    """The batched (vmapped) program must produce bitwise-identical
    decisions to per-cell ``fused_solve`` calls — mixed sizes, mixed
    hard/soft, one call."""
    rng = np.random.default_rng(0)
    reqs = [_request(rng, M=10 + 3 * k, soften=(k % 2 == 0))
            for k in range(6)]
    batch = fused_round_batch(reqs, devices=1)
    for r, b in zip(reqs, batch):
        single = fused_round.fused_solve(
            r.cost, r.allowed, r.capacity, soften=r.soften,
            overrun=r.overrun, tol=r.tol, sigma=r.sigma)
        assert b.backend == "fused"
        _assert_same_result(single, b)


def test_batch_matches_across_all_visible_devices():
    """Same contract with the shard_map path over every visible device
    (CI forces a 4-device host split; a 1-device box degrades to vmap)."""
    import jax

    n = len(jax.devices())
    rng = np.random.default_rng(1)
    reqs = [_request(rng, M=16, soften=False) for _ in range(2 * n)]
    batch = fused_round_batch(reqs, devices=n)
    for r, b in zip(reqs, batch):
        single = fused_round.fused_solve(
            r.cost, r.allowed, r.capacity, soften=r.soften,
            overrun=r.overrun, tol=r.tol, sigma=r.sigma)
        _assert_same_result(single, b)


def test_batch_devices_validation():
    import jax

    rng = np.random.default_rng(2)
    with pytest.raises(ValueError, match="exceeds"):
        fused_round_batch([_request(rng)], devices=len(jax.devices()) + 1)


def test_batch_infeasible_requests_short_circuit():
    """Per-request infeasibility (capacity shortfall, fully masked row)
    resolves exactly like ``fused_solve`` without touching the device."""
    rng = np.random.default_rng(3)
    good = _request(rng, M=8)
    short = _request(rng, M=8)
    short.capacity = np.full(4, 1, np.int64)         # sum 4 < 8 jobs
    masked = _request(rng, M=8)
    masked.allowed = np.zeros((8, 4), bool)
    out = fused_round_batch([good, short, masked], devices=1)
    assert out[0].feasible
    assert out[1].status == "infeasible" and not out[1].feasible
    assert out[2].status == "infeasible"
    for req, res in zip([short, masked], out[1:]):
        single = fused_round.fused_solve(req.cost, req.allowed, req.capacity,
                                         soften=req.soften,
                                         overrun=req.overrun, tol=req.tol,
                                         sigma=req.sigma)
        _assert_same_result(single, res)


def test_batch_compile_reuse_across_calls():
    """A second batch with the same (bucket, statics) signature reuses the
    compiled program — no retrace even for a different group size (padded
    to the same power-of-two batch shape)."""
    rng = np.random.default_rng(4)
    fused_round_batch([_request(rng, M=9) for _ in range(3)], devices=1)
    import jax

    fn = fused_round._batch_program(
        (jax.devices()[0],),
        **fused_round._request_statics(_request(rng, M=9)))
    before = fn._cache_size()
    fused_round_batch([_request(rng, M=11) for _ in range(4)], devices=1)
    assert fn._cache_size() == before        # same bucket 16, same batch 4


def test_fixed_slots_give_each_bucket_one_batch_shape():
    """With ``slots``, a group is padded to the same batch shape however
    many requests share its bucket; more than ``slots`` is an error."""
    import jax

    rng = np.random.default_rng(7)
    single = _request(rng, M=9)
    first = fused_round_batch([single], devices=1, slots=3)[0]
    fn = fused_round._batch_program(
        (jax.devices()[0],), **fused_round._request_statics(single))
    before = fn._cache_size()
    reqs = [_request(rng, M=m) for m in (9, 10, 11)]
    for out, group in ((fused_round_batch(reqs, devices=1, slots=3), reqs),
                       (fused_round_batch(reqs[:2], devices=1, slots=3),
                        reqs[:2])):
        for r, b in zip(group, out):
            _assert_same_result(fused_round.fused_solve(
                r.cost, r.allowed, r.capacity, soften=r.soften,
                overrun=r.overrun, tol=r.tol, sigma=r.sigma), b)
    assert fn._cache_size() == before        # bucket 16, 3 slots, once
    _assert_same_result(first, fused_round.fused_solve(
        single.cost, single.allowed, single.capacity, overrun=single.overrun,
        tol=single.tol, sigma=single.sigma))
    with pytest.raises(ValueError, match="more than slots"):
        fused_round_batch(reqs + [_request(rng, M=12)], devices=1, slots=3)
    with pytest.raises(ValueError, match="multiple"):
        fused_round_batch(reqs, devices=1, slots=0)


# ---------------------------------------------------------------------------
# group_requests invariants (pure bookkeeping, property-tested)
# ---------------------------------------------------------------------------

@given(st.lists(st.tuples(st.integers(1, 40),      # rows M
                          st.integers(2, 5),       # cols C
                          st.booleans(),           # soften
                          st.sampled_from([np.float32, np.float64])),
                min_size=1, max_size=16))
@settings(max_examples=50, deadline=None)
def test_group_requests_never_mixes_buckets_or_dtypes(shapes):
    rng = np.random.default_rng(5)
    reqs = [_request(rng, M=m, C=c, soften=s, dtype=dt)
            for m, c, s, dt in shapes]
    groups = group_requests(reqs)
    seen = sorted(i for idxs in groups.values() for i in idxs)
    assert seen == list(range(len(reqs)))     # exact cover, no dup/loss
    for key, idxs in groups.items():
        buckets = {bucket_for(reqs[i].cost.shape[0] + 1) for i in idxs}
        cols = {reqs[i].cost.shape[1] for i in idxs}
        dtypes = {np.asarray(reqs[i].cost).dtype for i in idxs}
        softs = {reqs[i].soften for i in idxs}
        assert len(buckets) == len(cols) == len(dtypes) == len(softs) == 1
        assert (bucket_for(reqs[idxs[0]].cost.shape[0] + 1),
                reqs[idxs[0]].cost.shape[1]) == key[:2]


def test_batch_size_is_device_multiple_power_of_two():
    assert fused_round._batch_size(1, 1) == 1
    assert fused_round._batch_size(3, 1) == 4
    assert fused_round._batch_size(5, 4) == 8
    assert fused_round._batch_size(8, 4) == 8
    assert fused_round._batch_size(9, 4) == 16


# ---------------------------------------------------------------------------
# Extended row buckets: >4096-job rounds solve and reuse compiles
# ---------------------------------------------------------------------------

def test_buckets_extend_to_16384_and_warn_once(recwarn):
    assert BUCKETS[-1] == 16384
    assert bucket_for(5000) == 8192
    assert bucket_for(16000) == 16384
    jax_solver._OVERFLOW_WARNED.discard(32768)
    with pytest.warns(RuntimeWarning, match="exceeds the largest padded"):
        assert bucket_for(20000) == 32768
    # second overflow of the same size is silent (warn once per size)
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert bucket_for(20000) == 32768


@pytest.mark.slow
def test_large_instance_solves_and_reuses_compile():
    """Regression for the old 4096 ceiling: a >4096-row instance lands in
    the 8192 bucket, solves correctly, and a second instance in the same
    bucket reuses the compile (no retrace)."""
    rng = np.random.default_rng(6)
    C = 4

    def solve(M):
        cost = rng.uniform(1.0, 5.0, (M, C))
        allowed = np.ones((M, C), bool)
        return fused_round.fused_solve(cost, allowed,
                                       np.full(C, M, np.int64))

    res = solve(4100)
    assert res.feasible and res.assign.shape == (4100,)
    from repro.kernels.sinkhorn import ops as sink_ops
    fn = fused_round._assignment_program
    before = fn._cache_size()
    res2 = solve(4200)                        # same 8192 bucket
    assert res2.feasible
    assert fn._cache_size() == before         # compile reuse across sizes
    del sink_ops


# ---------------------------------------------------------------------------
# The device executor backend
# ---------------------------------------------------------------------------

def test_device_executor_spec_grammar():
    ex = experiments.get_executor("device[devices=2,max_cells=8]")
    assert (ex.devices, ex.max_cells) == (2, 8)
    ex = experiments.get_executor("device")
    assert (ex.devices, ex.max_cells) == (0, 0)
    assert "device" in experiments.list_executors()


def test_device_executor_matches_serial_rows():
    """Acceptance: ``device`` ≡ ``serial`` bit-identical rows on a
    2-scenario × 2-policy plan — including the stateful forecast-driven
    policy, which cannot batch and must fall back cleanly."""
    plan = experiments.ExperimentPlan.build(
        scenarios=["diurnal[days=0.05,jobs_per_day=20000.0,tolerance=0.5]",
                   "nominal[days=0.05,jobs_per_day=20000.0]"],
        policies=["waterwise[backend=fused]", "waterwise-forecast"])
    serial = plan.run(executor="serial")
    device = plan.run(executor="device")
    assert len(serial) == len(device) == 4
    nondet = ("wall_s", "mean_solve_ms", "utilization")
    for s, d in zip(serial, device):
        assert not s["error"] and not d["error"]
        for key in s:
            if key in nondet or key.startswith("_"):
                continue
            assert s[key] == d[key], \
                f"column {key!r}: {s[key]} != {d[key]}"
        assert s["carbon_kg"] == d["carbon_kg"]
        assert s["water_kl"] == d["water_kl"]
        assert s["violation_pct"] == d["violation_pct"]


def test_device_executor_batchable_classification():
    from repro.experiments.executor import DeviceExecutor
    from repro.experiments.plan import Cell

    def cell(pol):
        return Cell(scenario="nominal", policy=pol, seed=0)

    assert DeviceExecutor._batchable(cell("waterwise[backend=fused]"))
    assert not DeviceExecutor._batchable(cell("waterwise"))  # default: flow
    assert not DeviceExecutor._batchable(cell("waterwise[backend=flow]"))
    assert not DeviceExecutor._batchable(cell("waterwise-forecast"))
    assert not DeviceExecutor._batchable(cell("baseline"))
    assert not DeviceExecutor._batchable(cell("no-such-policy"))


def test_cell_batcher_flushes_on_finish_and_broadcasts_errors():
    """Barrier liveness: a finishing thread flushes waiters; a flush
    exception reaches every waiting submit."""
    from repro.core.lockstep import LockstepBatcher

    calls = []

    def flush(reqs):
        calls.append(len(reqs))
        return [r * 10 for r in reqs]

    b = LockstepBatcher(flush)
    b.register()
    assert b.submit(7) == 70                 # active=1 → immediate flush
    b.finish()
    assert calls == [1]

    def boom(reqs):
        raise RuntimeError("device exploded")

    b = LockstepBatcher(boom)
    b.register()
    with pytest.raises(RuntimeError, match="device exploded"):
        b.submit(1)
    b.finish()


# ---------------------------------------------------------------------------
# Safe XLA host-platform flag configuration (repro.launch.devices)
# ---------------------------------------------------------------------------

def test_merge_xla_flag_preserves_other_flags():
    merged = launch_devices.merge_xla_flag(
        "--xla_cpu_foo=1 --xla_force_host_platform_device_count=2 --bar",
        "--xla_force_host_platform_device_count", 8)
    assert merged == ("--xla_cpu_foo=1 --bar "
                      "--xla_force_host_platform_device_count=8")
    assert launch_devices.merge_xla_flag(
        None, "--xla_force_host_platform_device_count", 4) == \
        "--xla_force_host_platform_device_count=4"
    # valueless occurrence of the same flag is also replaced
    assert launch_devices.merge_xla_flag(
        "--f", "--f", 3) == "--f=3"


def test_set_host_platform_device_count_rejects_bad_n():
    with pytest.raises(ValueError, match=">= 1"):
        launch_devices.set_host_platform_device_count(0)


def test_set_host_platform_device_count_after_backend_init():
    """This test file has long since initialized the backend — setting a
    *different* count must raise (strict) or warn-and-return-False, never
    silently no-op; re-asserting the live count is fine."""
    import jax

    live = len(jax.devices())
    assert launch_devices.backend_initialized()
    assert launch_devices.set_host_platform_device_count(live) is True
    with pytest.raises(RuntimeError, match="already initialized"):
        launch_devices.set_host_platform_device_count(live + 1)
    assert launch_devices.set_host_platform_device_count(
        live + 1, strict=False) is False
