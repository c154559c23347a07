"""The benchmark finds every file by name, and refuses what it cannot run."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = pathlib.Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    ends = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in ends
    for m in BENCH["per_layer"]:
        assert m["moves"] in ends


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    w = harness.workload(BENCH, cell)
    config = harness.config_of(BENCH, w["config"])
    traffic = harness.traffic_of(w["traffic"])
    limits = harness.limits_of(cell)
    assert config["reduced"] == []
    assert traffic["round_s"] > 0 and limits
    for traced in (False, True):
        for m in harness.metrics_for(BENCH, cell, traced):
            assert callable(harness.metric_reader(m["name"]))
    untraced = {m["name"] for m in harness.metrics_for(BENCH, cell, False)}
    assert {"setup_s", "jobs_per_s"} <= untraced


def test_unknown_names_and_device_kinds_are_errors():
    with pytest.raises(harness.BenchError):
        harness.workload(BENCH, "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.traffic_of("no-such-mix")
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no_such_metric")
    with pytest.raises(harness.BenchError, match="not in"):
        harness.peaks_of("TPU v99 imaginary")
    assert harness.peaks_of("TPU v5 lite")["flops_per_s"] == 197e12


def test_readers_report_nothing_without_data():
    run = harness.Run(setup_s=1.0, window_s=2.0, rounds=[], placed=0,
                      solves=[])
    for m in BENCH["per_layer"]:
        assert harness.metric_reader(m["name"])(run) is None


def test_new_cells_need_new_files_only(tmp_path, monkeypatch):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    (tmp_path / "chipbench" / "traffic" / "burst30.json").write_text(
        json.dumps(dict(harness.traffic_of("cadence30"), burst=1.0)))
    (tmp_path / "chipbench" / "limits" / "waterwise-cell.burst30.json"
     ).write_text(json.dumps({"plan_gap": 0.05}))
    (tmp_path / "chipbench" / "metrics" / "round_count.py").write_text(
        "def read(run):\n    return len(run.rounds) or None\n")
    bench["workloads"].append(dict(name="waterwise-cell.burst30",
                                   config="waterwise-cell",
                                   traffic="burst30", chips=1, why="x"))
    bench["per_layer"].append(dict(name="round_count", unit="rounds",
                                   better="higher", source="host_clock",
                                   layer="serve loop and engine",
                                   moves="jobs_per_s",
                                   workloads=["waterwise-cell.burst30"]))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "HERE", tmp_path / "chipbench")
    w = harness.workload(bench, "waterwise-cell.burst30")
    assert harness.traffic_of(w["traffic"])["burst"] == 1.0
    assert harness.limits_of(w["name"]) == {"plan_gap": 0.05}
    names = [m["name"] for m in harness.metrics_for(bench, w["name"], True)]
    assert "round_count" in names
    run = harness.Run(setup_s=1.0, window_s=2.0, rounds=[1, 2], placed=0,
                      solves=[])
    assert harness.metric_reader("round_count")(run) == 2


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


ARGS = ("--workload", "waterwise-cell.cadence30", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0")


def test_refuses_without_a_tpu():
    p = _run(ROOT, *ARGS)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, *ARGS)
    assert p.returncode != 0
    assert p.stdout == ""
