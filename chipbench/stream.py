"""The benchmark's own copy of the job-stream generator.

Copied from ``repro.serve.arrivals.PoissonBurstArrivals`` and
``repro.sim.trace._make_jobs`` / ``BENCHMARK_PROFILES`` so that a change to
the program's generator cannot move the yardstick. Same seed, same jobs:
``tests/test_bench_stream.py`` pins the copy to the program's generator.

Arrivals are an inhomogeneous Poisson process (diurnal sine of depth
``diurnal_depth``; 30-minute hot windows every 4 h multiplying the rate by
``1 + 4·burst``), generated in hourly chunks, chunk ``c`` from
``default_rng((seed, c))``. Every job takes one profile of the paper's
PARSEC/CloudSuite mix (Table 1) with a log-normal duration jitter.

``Stream`` generates its whole span when it is made, so that a run pays
for generation in set-up and never inside its window. Its jobs come from
the traffic's fixed ``stream_seed``; the run's seed only deals each period
of ``shuffle_s`` seconds (ending ``phase`` of a period past each multiple,
where the harness's rounds end) its own jobs over its own arrival times in
another order. So every seed brings each round the same set of sizes and
arrivals, and seeds differ in the order alone.

Where a configuration states several fleets, fleet ``i`` has a stream of
its own: its jobs come from ``stream_seed + i``, and its periods are dealt
from ``(seed, c)`` for fleet 0, which is the one-fleet stream, and from
``(seed, c, i)`` for every other fleet.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core.problem import Job

DAY = 86400.0
CHUNK_S = 3600.0

# (name, exec seconds, mean power W, package bytes): paper Table 1 mix.
PROFILES = (
    ("dedup", 210.0, 340.0, 1.8e9),
    ("netdedup", 260.0, 350.0, 1.9e9),
    ("canneal", 680.0, 290.0, 0.9e9),
    ("blackscholes", 380.0, 310.0, 0.6e9),
    ("swaptions", 420.0, 330.0, 0.5e9),
    ("data-caching", 900.0, 260.0, 2.5e9),
    ("graph-analytics", 1500.0, 380.0, 3.2e9),
    ("web-serving", 1100.0, 240.0, 2.8e9),
    ("memory-analytics", 1300.0, 360.0, 3.0e9),
    ("media-streaming", 800.0, 270.0, 4.5e9),
)
REGION_WEIGHTS = (0.25, 0.30, 0.15, 0.15, 0.15)


def make_jobs(rng: np.random.Generator, arrivals: np.ndarray,
              num_regions: int, tolerance: float, duration_jitter: float,
              order=None) -> List[Job]:
    """One job per arrival time: profile, home region and jitter drawn from
    ``rng`` in the program generator's order, then dealt to the arrival
    times in ``order`` where one is given."""
    picks = rng.integers(0, len(PROFILES), arrivals.size)
    w = np.array(REGION_WEIGHTS[:num_regions])
    homes = rng.choice(num_regions, size=arrivals.size, p=w / w.sum())
    jitter = rng.lognormal(mean=0.0, sigma=duration_jitter,
                           size=arrivals.size)
    if order is not None:
        picks, homes, jitter = picks[order], homes[order], jitter[order]
    jobs = []
    for i, (ts, k, h, jt) in enumerate(zip(arrivals, picks, homes, jitter)):
        name, exec_s, power_w, tar = PROFILES[k]
        jobs.append(Job(job_id=i, home_region=int(h), submit_time_s=float(ts),
                        exec_time_s=float(exec_s * jt),
                        energy_kwh=float(power_w * exec_s / 3.6e6 * jt),
                        package_bytes=tar, tolerance=tolerance, arch=name))
    return jobs


class Stream:
    """Seeded job stream over [0, ``span_s``), generated when it is made.
    With ``shuffle_s``, ``shuffle_seed`` and ``fleet`` deal each period's
    jobs over its arrival times (module docstring)."""

    def __init__(self, jobs_per_day: float, *, seed: int, num_regions: int,
                 tolerance: float, diurnal_depth: float, burst: float,
                 duration_jitter: float, span_s: float,
                 shuffle_s: float = None, shuffle_seed: int = 0,
                 phase: float = 0.0, fleet: int = 0):
        rate = float(jobs_per_day) / DAY
        lam_max = rate * (1 + diurnal_depth) * (1 + burst * 4)
        self.span_s = float(span_s)
        self.jobs: List[Job] = []
        for c in range(int(np.ceil(self.span_s / CHUNK_S))):
            t0 = c * CHUNK_S
            rng = np.random.default_rng((int(seed), c))
            n_cand = rng.poisson(lam_max * CHUNK_S)
            t = np.sort(rng.uniform(t0, t0 + CHUNK_S, n_cand))
            lam = rate * (1 + diurnal_depth * np.sin(t / DAY * 2 * np.pi))
            if burst > 0:
                phase = (t % (4 * 3600.0)) < 1800.0
                lam = lam * np.where(phase, 1 + 4 * burst, 1.0)
            keep = rng.uniform(0, lam_max, n_cand) < lam
            order = None
            if shuffle_s:
                period = np.floor(t[keep] / shuffle_s + 1.0 - phase)
                deal = np.random.default_rng(
                    (int(shuffle_seed), c) + ((int(fleet),) if fleet else ()))
                order = np.lexsort((deal.random(period.size), period))
            for j in make_jobs(rng, t[keep], num_regions, tolerance,
                               duration_jitter, order):
                j.job_id = len(self.jobs)
                self.jobs.append(j)
        self._i = 0                     # first job not yet polled

    @classmethod
    def from_traffic(cls, traffic: dict, seed: int, num_regions: int,
                     span_s: float, phase: float,
                     fleet: int = 0) -> "Stream":
        """Fleet ``fleet``'s stream of the traffic, its periods
        (``round_s``, ending ``phase`` of a period past each multiple)
        dealt by the run's ``seed`` (module docstring)."""
        return cls(traffic["jobs_per_day"],
                   seed=traffic["stream_seed"] + fleet,
                   num_regions=num_regions, tolerance=traffic["tolerance"],
                   diurnal_depth=traffic["diurnal_depth"],
                   burst=traffic["burst"],
                   duration_jitter=traffic["duration_jitter"], span_s=span_s,
                   shuffle_s=traffic["round_s"], shuffle_seed=seed,
                   phase=phase, fleet=fleet)

    def poll(self, until_s: float) -> List[Job]:
        """Jobs submitted before ``until_s`` not returned yet, in order."""
        if until_s > self.span_s:
            raise ValueError(f"the stream ends at {self.span_s} s, polled "
                             f"to {until_s} s")
        jobs, i = self.jobs, self._i
        cut = i
        while cut < len(jobs) and jobs[cut].submit_time_s < until_s:
            cut += 1
        self._i = cut
        return jobs[i:cut]
