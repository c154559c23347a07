"""The Pallas Sinkhorn kernel's share of its roofline where the fleets'
solves run batched over several chips.

Counted as ``sinkhorn_roofline.py`` counts it, from the problem's true
shape: each iteration touches every element of each answered call's
``(M+1) x C`` cost matrix 12 times, and each solve reads the cost matrix
and both marginals and writes both potentials once, in float32; padding
rows, repeated slots and lanes are not counted. The iterations per solve
are the window's ``solver.batch_sinkhorn_iters`` over its
``round.batch_solves``; the work, divided by the chips a batched dispatch
spans (``solver.batch_chips`` over the ``solver.batch_device`` spans), is
one chip's share, and the least time of that share over the peaks
(``peaks.json``) is divided by the kernel's device seconds, which the
trace gives as the mean over the cell's chips. None where the program has
no such counters or the kernel did not run."""
from chipbench import obs_session

OPS_PER_ELEMENT = 12
BYTES = 4


def read(run):
    s = obs_session.of(run)
    if s is None or not run.solves:
        return None
    kernel_s = run.trace.kernel_s.get("sinkhorn_iteration_pallas")
    c = s["counters"]
    iters, solves = c.get("solver.batch_sinkhorn_iters"), \
        c.get("round.batch_solves")
    chips = c.get("solver.batch_chips")
    waves = s["spans"].get("solver.batch_device", (0, 0.0))[0]
    if not kernel_s or not iters or not solves or not chips or not waves:
        return None
    per_solve = iters / solves
    ops = nbytes = 0.0
    for sv in run.solves:
        rows, cols = sv["rows"] + 1, sv["cols"]
        ops += per_solve * rows * cols * OPS_PER_ELEMENT
        nbytes += BYTES * (rows * cols + 2 * (rows + cols))
    per_chip = chips / waves
    least = max(ops / per_chip / run.peaks["flops_per_s"],
                nbytes / per_chip / run.peaks["bytes_per_s"])
    return 100.0 * least / kernel_s
