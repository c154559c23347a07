"""Share of the rows the batched device program solved that were real:
over the window's waves, the true rows (each batched cell's jobs plus its
balancing row; counter ``solver.batch_rows``) over the rows solved, every
slot at its row bucket, repeated slots included (counter
``solver.batch_padded_rows``). None where the program has no such
counters or batched nothing."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None:
        return None
    rows = s["counters"].get("solver.batch_rows")
    padded = s["counters"].get("solver.batch_padded_rows")
    if not rows or not padded:
        return None
    return 100.0 * rows / padded
