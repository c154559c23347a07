"""The Pallas Sinkhorn kernel's share of its roofline.

Least time over the kernel's summed device time in the trace. The work is
counted from the problem, not from what ran: each executed iteration (the
solve span's ``sinkhorn_iters``) touches every element of the true
``(M+1) x C`` cost matrix (M jobs, the balancing row, C columns) twice,
once in the row and once in the column log-sum-exp, six operations each
(subtract, scale, running max, shift, exp, add), so 12 per element;
padding rows and lanes are not counted. The bytes are one read per solve
of the cost matrix and both marginals and one write of both potentials,
in float32. The least time is the larger of operations over the peak
FLOP/s and bytes over the peak bandwidth (``peaks.json``); at these shapes
the operations bound it. None where the kernel did not run or a solve's
iteration count is unknown.
"""

OPS_PER_ELEMENT = 12
BYTES = 4


def read(run):
    if run.trace is None or not run.solves:
        return None
    kernel_s = run.trace.kernel_s.get("sinkhorn_iteration_pallas")
    if not kernel_s or any("iters" not in s for s in run.solves):
        return None
    ops = nbytes = 0.0
    for s in run.solves:
        rows, cols = s["rows"] + 1, s["cols"]
        ops += s["iters"] * rows * cols * OPS_PER_ELEMENT
        nbytes += BYTES * (rows * cols + 2 * (rows + cols))
    least = max(ops / run.peaks["flops_per_s"],
                nbytes / run.peaks["bytes_per_s"])
    return 100.0 * least / kernel_s
