"""Mean per answered call of the program's ``solver.device`` seconds: from
the fused program's dispatch through the copy of its outputs to the host,
the time the host waits for the device and the transfer. Every solve of
the window runs inside an answered call, so this is the window's summed
span seconds over the answered calls. None where the program has no such
span."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "solver.device" not in s["spans"] or not run.solves:
        return None
    return 1e3 * obs_session.seconds(s, "solver.device") / len(run.solves)
