"""Mean per answered call of the program's ``solver.finalize`` seconds: the
host's rounding of the plan to an assignment, the SSP repair where taken
and the 2-swap polish (``jax_solver._finalize``). Every solve of the window
runs inside an answered call, so this is the window's summed span seconds
over the answered calls. None where the program has no such span."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "solver.finalize" not in s["spans"] or not run.solves:
        return None
    return 1e3 * obs_session.seconds(s, "solver.finalize") / len(run.solves)
