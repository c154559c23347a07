"""Mean seconds per answered call of the program's solve spans
(``solver.solve``, ``solver.fused_round``): dispatch of the fused program,
device time, the device-to-host copy and the host's rounding and polish.
Read from the traced run's ``repro.obs`` spans."""


def read(run):
    timed = [s["span_s"] for s in run.solves if "span_s" in s]
    if not timed:
        return None
    return 1e3 * sum(timed) / len(timed)
