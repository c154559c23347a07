"""The always-on scheduler service in ~50 lines: a Poisson-burst arrival
storm streamed through the bounded admission queue into the fused
warm-started ``waterwise-forecast`` pipeline, one decision round per
boundary, with the full service report (stream accounting, queue depths,
p50/p99 round latency, cold vs warm Sinkhorn iterations) at the end.

  PYTHONPATH=src python examples/serve_stream.py                # ~1 min
  PYTHONPATH=src python examples/serve_stream.py --duration 30 \\
      --round-s 5 --assert-clean                                # CI smoke

``--queue-bound 20`` makes the storm actually shed (accounted, never
silent — shed jobs are deadline misses in the report); ``--assert-clean``
exits non-zero unless the service finished with zero deadline misses and
non-empty round metrics.
"""
import argparse
import sys

import repro.obs as obs
from repro.core import telemetry
from repro.policy.pipeline import forecast_pipeline
from repro.runtime.platform import use_compile_cache
from repro.serve import DecisionLoop, PoissonBurstArrivals, ServeConfig
from repro.sim.engine import EventSimulator, SimConfig
from repro.sim.trace import scale_capacity_for_utilization


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=600.0,
                    help="simulated seconds to serve")
    ap.add_argument("--jobs-per-day", type=float, default=1e5)
    ap.add_argument("--round-s", type=float, default=30.0,
                    help="decision-round period (simulated seconds)")
    ap.add_argument("--queue-bound", type=int, default=10_000)
    ap.add_argument("--shed-policy", default="reject-new",
                    choices=["reject-new", "drop-oldest"])
    ap.add_argument("--burst", type=float, default=1.0,
                    help="burst-train amplitude (0 = plain diurnal Poisson)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--assert-clean", action="store_true",
                    help="exit 1 unless zero deadline misses and non-empty "
                         "round metrics (the CI smoke contract)")
    args = ap.parse_args()
    use_compile_cache()

    tele = telemetry.generate(days=1, seed=0)
    rate = args.jobs_per_day / 86400.0
    src = PoissonBurstArrivals(rate, seed=args.seed,
                               num_regions=tele.num_regions, tolerance=4.0,
                               burst=args.burst, horizon_s=args.duration)
    probe = PoissonBurstArrivals(rate, seed=args.seed,
                                 num_regions=tele.num_regions, tolerance=4.0,
                                 burst=args.burst, horizon_s=args.duration)
    cap = scale_capacity_for_utilization(probe.poll(args.duration),
                                         args.duration / 86400.0,
                                         tele.num_regions, 0.15)
    ctl = forecast_pipeline(tele, forecaster="oracle", risk=0.0,
                            slot_s=1800.0, defer_eps=1e-4, backend="fused",
                            warm=True)
    loop = DecisionLoop(EventSimulator(tele, cap, SimConfig()), ctl, src,
                        ServeConfig(round_s=args.round_s,
                                    queue_bound=args.queue_bound,
                                    shed_policy=args.shed_policy))
    print(f"serving {args.duration:.0f}s of a {args.jobs_per_day:.0f} "
          f"jobs/day storm (burst={args.burst}, round={args.round_s:.0f}s, "
          f"queue bound {args.queue_bound}, {args.shed_policy})")
    with obs.capture(fold=False) as reg:
        rep = loop.run(args.duration)
    for k, v in sorted(rep.to_dict().items()):
        print(f"  {k:>22} = {v:.3f}" if isinstance(v, float)
              else f"  {k:>22} = {v}")
    rounds = reg.hists.get("serve.round_wall_ms")
    if rep.deadline_misses == 0 and rounds is not None and rounds.count > 0:
        print(f"OK: {rep.placed} jobs placed, zero deadline misses, "
              f"{rounds.count} instrumented rounds")
        return 0
    print(f"service finished with {rep.deadline_misses} deadline misses "
          f"({rep.shed} shed, {rep.violations} over tolerance)")
    return 1 if args.assert_clean else 0


if __name__ == "__main__":
    sys.exit(main())
