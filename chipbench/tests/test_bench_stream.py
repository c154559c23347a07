"""The benchmark's copy of the job stream is the program's generator."""
import numpy as np
import pytest

from chipbench.stream import Stream
from repro.serve import PoissonBurstArrivals

FIELDS = ("job_id", "home_region", "submit_time_s", "exec_time_s",
          "energy_kwh", "package_bytes", "tolerance", "arch")


def _rows(jobs):
    return [tuple(getattr(j, f) for f in FIELDS) for j in jobs]


@pytest.mark.parametrize("seed,depth,burst", [(0, 0.0, 0.0),
                                              (2 ** 31 + 12345, 0.45, 1.0),
                                              (7, 0.3, 0.5)])
def test_stream_matches_program_generator(seed, depth, burst):
    kw = dict(seed=seed, num_regions=5, tolerance=1.0, diurnal_depth=depth,
              burst=burst, duration_jitter=0.35)
    ours = Stream(2e5, span_s=9000.0, **kw)
    theirs = PoissonBurstArrivals(2e5 / 86400.0, **kw)
    for until in (30.0, 4000.0, 4000.0, 9000.0):
        assert _rows(ours.poll(until)) == _rows(theirs.poll(until))


def test_stream_is_deterministic_by_seed_and_independent_of_polling():
    def stream(seed):
        return Stream(1e6, seed=seed, num_regions=5, tolerance=0.5,
                      diurnal_depth=0.0, burst=0.0, duration_jitter=0.35,
                      span_s=7200.0)
    a, b = stream(3000000017), stream(3000000017)
    coarse = a.poll(7200.0)
    fine = [j for k in range(1, 241) for j in b.poll(30.0 * k)]
    assert _rows(coarse) == _rows(fine)
    assert len(coarse) > 70000
    other = stream(3000000018).poll(7200.0)
    assert _rows(other[:50]) != _rows(coarse[:50])
    assert np.all(np.diff([j.submit_time_s for j in coarse]) >= 0)


def test_stream_is_generated_whole_and_ends_at_its_span():
    s = Stream(1e6, seed=1, num_regions=5, tolerance=0.5, diurnal_depth=0.0,
               burst=0.0, duration_jitter=0.35, span_s=5400.0)
    n = len(s.jobs)
    assert n > 0 and s.jobs[-1].submit_time_s < 7200.0
    assert [j.job_id for j in s.jobs] == list(range(n))
    got = s.poll(5400.0)
    assert len(s.jobs) == n            # polling generates nothing
    assert got and all(j.submit_time_s < 5400.0 for j in got)
    with pytest.raises(ValueError):
        s.poll(5400.5)


def test_seeds_deal_each_period_the_same_jobs_in_another_order():
    def stream(seed):
        return Stream(1e6, seed=0, num_regions=5, tolerance=0.5,
                      diurnal_depth=0.0, burst=0.0, duration_jitter=0.35,
                      span_s=3600.0, shuffle_s=30.0, shuffle_seed=seed,
                      phase=0.5)

    a, b = stream(3000000001), stream(2 ** 32 + 5)
    plain = Stream(1e6, seed=0, num_regions=5, tolerance=0.5,
                   diurnal_depth=0.0, burst=0.0, duration_jitter=0.35,
                   span_s=3600.0)
    assert [j.submit_time_s for j in a.jobs] == [
        j.submit_time_s for j in plain.jobs]
    for k in range(1, 120):
        lo, hi = (k - 0.5) * 30.0, (k + 0.5) * 30.0
        sets = [sorted((j.arch, j.home_region, j.exec_time_s)
                       for j in s.jobs if lo <= j.submit_time_s < hi)
                for s in (a, b, plain)]
        assert sets[0] == sets[1] == sets[2]
    assert _rows(a.jobs) != _rows(b.jobs)
    assert _rows(a.jobs) == _rows(stream(3000000001).jobs)
