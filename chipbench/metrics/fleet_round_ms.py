"""Mean wall of one boundary of several fleets, read inside the program:
the summed seconds of the ``serve.fleet_round`` spans over their count
(``serve.FleetLoop.run_round``: every fleet's round, their solves' device
wave and the host rounding). None where the program has no such span."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "serve.fleet_round" not in s["spans"]:
        return None
    n, seconds = s["spans"]["serve.fleet_round"]
    return 1e3 * seconds / n
