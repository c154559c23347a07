#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metric readers are
found by name from ``BENCHMARK.json`` (see ``chipbench/README.md``). Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero before
any round. The last line of standard output is one JSON object; the numbers
compared for ``correct`` are printed beside their limits as the last lines
of standard error and, under ``checks``, last in that object.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time


def _process_start() -> float:
    """``perf_counter`` reading at which this process started."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_START = _process_start()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def prepare(workload: str, traced: bool):
    """The cell's files, found by name, and the device it runs on. Raises
    ``SystemExit(2)`` where JAX finds no TPU or too few chips."""
    from chipbench import harness
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.workload(bench, workload)
    files = dict(config=harness.config_of(bench, cell["config"]),
                 traffic=harness.traffic_of(cell["traffic"]),
                 limits=harness.limits_of(cell["name"]),
                 metrics=harness.metrics_for(bench, cell["name"], traced))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < cell["chips"]:
        print(f"chipbench: the cell needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    harness.peaks_of(devices[0].device_kind)
    # The persistent compilation cache lives at a fixed path inside the
    # checkout, whatever the environment says, so that only a checkout's
    # first run compiles and two checkouts never share it.
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    files["device"] = dict(platform=devices[0].platform,
                           kind=devices[0].device_kind, count=len(devices),
                           chips=cell["chips"])
    return files


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    result = harness.run_cell(**prepare(args.workload, bool(args.trace)),
                              seed=args.seed, seconds=args.seconds,
                              traced=bool(args.trace), t_start=T_START)
    for name, v in result["readings"].items():
        print(f"reading {name} {v!r}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
