"""Per window boundary: the program's ``solver.batch_device`` seconds, from
each batched group's dispatch through the copy of its outputs to the host,
the time the host waits for the fleets' device wave. None where the
program has no such span."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "solver.batch_device" not in s["spans"] \
            or not run.rounds:
        return None
    return 1e3 * obs_session.seconds(s, "solver.batch_device") \
        / len(run.rounds)
