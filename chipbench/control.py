#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's sound runs and the
control's, all in one process on the chip.

  python3 chipbench/control.py --workload <cell> --seconds <s> \\
      --sound 11,12,13 --control 21,22,23 [--fault cost_blind]

Each run is a whole benchmark run of the cell at its own size; the control
runs it with ``--fault`` (one of ``faults.FAULTS``; ``cost_blind``, the
control, unless named) planted underneath. One JSON line per run on
standard output: the seed, which side, ``correct`` and every number
compared. The benchmark's own runs never plant a fault.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run  # noqa: E402  (sets the import path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="cost_blind")
    args = ap.parse_args(argv)

    from chipbench import faults, harness
    fault = faults.FAULTS[args.fault]
    files = bench_run.prepare(args.workload, traced=False)
    plan = [("sound", int(s)) for s in args.sound.split(",") if s]
    plan += [("control", int(s)) for s in args.control.split(",") if s]
    for side, seed in plan:
        t0 = time.perf_counter()
        if side == "sound":
            res = harness.run_cell(**files, seed=seed, seconds=args.seconds,
                                   traced=False, t_start=t0)
        else:
            with fault():
                res = harness.run_cell(**files, seed=seed,
                                       seconds=args.seconds, traced=False,
                                       t_start=t0)
        print(json.dumps(dict(
            side=side if side == "sound" else args.fault, seed=seed,
            correct=res["correct"],
            checks={k: v["value"] for k, v in res["checks"].items()},
            readings=res["readings"],
            metrics={k: v["value"] for k, v in res["metrics"].items()},
            wall_s=time.perf_counter() - t0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
