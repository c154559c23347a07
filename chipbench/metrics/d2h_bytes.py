"""Bytes the solve path copied from the device per answered call: the
window increase of the program counter ``solver.d2h_bytes`` over the
answered calls. None where the program has no such counter."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "solver.d2h_bytes" not in s["counters"] \
            or not run.solves:
        return None
    return s["counters"]["solver.d2h_bytes"] / len(run.solves)
