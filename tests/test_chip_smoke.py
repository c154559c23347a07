"""``chip_smoke.py`` phases at a tiny size on the CPU.

The same phase functions the chip runs, on ~200 jobs with 30 s rounds, the
Pallas Sinkhorn in interpret mode, and the same checks: nothing shed, no
placement over capacity or on a masked arc, the kernel mode read from the
counters, and every recorded round of A and B within 2 % of ``flow``.
"""
import importlib.util
import json
import pathlib
import sys

import jax
import pytest

from repro.core import round as fused_round

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module       # dataclasses resolve it by name
    spec.loader.exec_module(module)
    return module


@pytest.fixture()
def pallas_interpret(monkeypatch):
    """The fused round's default Sinkhorn set to the Pallas kernel, which
    runs in interpret mode off the TPU."""
    monkeypatch.setattr(fused_round, "sinkhorn_impl_default",
                        lambda: "pallas")


def _tiny(smoke):
    return smoke.Traffic(jobs_per_day=1e5, window_s=30.0, rounds=6)


@pytest.mark.parametrize("name,kernel,forbid", [
    ("A", "pallas_interpret", ("pallas", "xla")),
    ("B", "pallas_interpret", ("pallas", "xla")),
    ("C", "xla", ("pallas",)),
])
def test_phase_on_cpu(smoke, pallas_interpret, name, kernel, forbid):
    with smoke.CompileClock() as clock:
        traffic = _tiny(smoke)
        res = smoke.run_phase(name, smoke.PHASES[name], traffic,
                              kernel=kernel, forbid=forbid, min_rows=16,
                              reference=name != "C", clock=clock)
    assert 150 <= res["jobs"] <= 300
    assert res["placed"] == res["jobs"] and res["shed"] == 0
    assert res["sinkhorn"][kernel] > 0
    if name != "C":
        assert res["flow_rounds"] > 0
        assert res["max_flow_gap"] <= smoke.GAP_LIMIT
        assert res["max_plan_gap"] <= smoke.PLAN_GAP_LIMIT
        assert abs(res["control_plan_gap"]) > smoke.PLAN_GAP_LIMIT


def test_phases_at_300s_rounds_keep_every_deadline(smoke):
    """At the chip's 300 s rounds the policies as specified keep every job
    inside its Eq-11 deadline: their slack reserves grow to two round
    periods (``PolicyPipeline.reserve_s``)."""
    traffic = smoke.Traffic(jobs_per_day=5e4, window_s=300.0, rounds=3)
    with smoke.CompileClock() as clock:
        for name in ("A", "B"):
            res = smoke.run_phase(name, smoke.PHASES[name], traffic,
                                  kernel="xla", forbid=("pallas",),
                                  min_rows=16, reference=True, clock=clock)
            assert res["deadline_misses"] == 0 and res["shed"] == 0


def test_device_executor_phase_matches_serial(smoke):
    n = len(jax.devices())
    res = smoke.device_executor_phase(
        smoke.Traffic(jobs_per_day=2e4, window_s=30.0, rounds=4),
        cells=2 * n, devices=n)
    assert res["shard_devices"] == n and res["cells_per_device"] == [2]
    assert res["jobs"] > 0


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert "not a TPU" in out.err
    assert out.out == ""
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.out or "-")
