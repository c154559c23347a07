"""Per round: ``run_round`` wall minus the scheduler's ``schedule()`` wall,
the serve loop and engine's own share (admission, injection, dispatch)."""


def read(run):
    if not run.rounds:
        return None
    return 1e3 * sum(r.wall_s - r.schedule_s
                     for r in run.rounds) / len(run.rounds)
