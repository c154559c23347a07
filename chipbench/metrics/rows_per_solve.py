"""Mean job rows of the solve that answered each call, from the ``jobs`` of
the program's solve span: shows when a change altered the decisions'
inputs rather than the speed."""


def read(run):
    rows = [s["span_rows"] for s in run.solves if "span_rows" in s]
    if not rows:
        return None
    return sum(rows) / len(rows)
