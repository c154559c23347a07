"""Per answered call: the harness's host clock around ``schedule()`` minus
the program's solve spans inside it, the pipeline's own share (admit,
build, host pricing, extract)."""


def read(run):
    timed = [s["schedule_s"] - s["span_s"] for s in run.solves
             if "span_s" in s]
    if not timed:
        return None
    return 1e3 * sum(timed) / len(timed)
