"""Jobs placed in the window's rounds over the window's wall span."""


def read(run):
    return run.placed / run.window_s
