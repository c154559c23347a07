"""Per answered call: the program's ``policy.schedule`` span minus the solve
spans inside it (``solver.solve``, ``solver.fused_round``), the pipeline's
own share (admit, build, host pricing, forecast, extract), read inside the
program. Every solve of the window runs inside a ``policy.schedule`` span,
so this is the window's summed ``policy.schedule`` seconds less its summed
solve seconds, over the answered calls. None where the program has no
``policy.schedule`` span."""
from chipbench import obs_session

SOLVE_SPANS = ("solver.solve", "solver.fused_round")


def read(run):
    s = obs_session.of(run)
    if s is None or "policy.schedule" not in s["spans"] or not run.solves:
        return None
    own = obs_session.seconds(s, "policy.schedule") \
        - obs_session.seconds(s, *SOLVE_SPANS)
    return 1e3 * own / len(run.solves)
