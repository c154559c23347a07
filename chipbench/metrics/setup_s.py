"""Seconds from process start to the window: JAX start, telemetry, stream,
policy build with the forecaster's first fit, compiles or cache loads of
the cell's programs, and the warm-up rounds."""


def read(run):
    return run.setup_s
