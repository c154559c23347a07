"""Milliseconds of garbage collection per window round: the window increase
of the program counter ``host.gc_s`` (every collection's seconds) over the
window's rounds. None where the program has no such counter."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "host.gc_s" not in s["counters"] or not run.rounds:
        return None
    return 1e3 * s["counters"]["host.gc_s"] / len(run.rounds)
