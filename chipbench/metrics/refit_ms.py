"""Mean seconds of the program's ``forecast.fit`` spans in the window: one
refit of the forecaster (the Holt-Winters fit each simulated hour). None
where no refit ran or the program has no such span."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "forecast.fit" not in s["spans"]:
        return None
    fits, seconds = s["spans"]["forecast.fit"]
    return 1e3 * seconds / fits
