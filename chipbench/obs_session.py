"""What the program's ``repro.obs`` recorded over a traced run's window.

A traced run enables ``repro.obs`` for the window alone: the profiler's
start and stop lie inside the session, and no program work outside it. So
the latest obs session (``repro.obs.session()``) is the window: each
counter's increase and each span name's count and summed seconds. Readers
of ``program_span`` and ``program_counter`` metrics read it; an untraced
run, or a program whose ``repro.obs`` keeps no session, gives them nothing.
"""


def of(run):
    """The traced window's obs session, or None."""
    if run.trace is None:
        return None
    import repro.obs as obs
    session = getattr(obs, "session", None)
    return None if session is None else session()


def seconds(session, *names) -> float:
    """Summed seconds of the session's spans called any of ``names``."""
    return sum(session["spans"][n][1] for n in names
               if n in session["spans"])
