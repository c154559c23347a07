"""Per window round: the program's ``serve.round`` span minus the
``policy.schedule`` spans inside it, the serve loop and engine's own share
(admission, injection, dispatch, accounting), read inside the program. The
served loop calls the pipeline only inside its rounds, so this is the
window's summed ``serve.round`` seconds less its summed ``policy.schedule``
seconds, over its rounds. None where the program has no ``policy.schedule``
span."""
from chipbench import obs_session


def read(run):
    s = obs_session.of(run)
    if s is None or "policy.schedule" not in s["spans"] \
            or "serve.round" not in s["spans"]:
        return None
    rounds, seconds = s["spans"]["serve.round"]
    return 1e3 * (seconds - obs_session.seconds(s, "policy.schedule")) \
        / rounds
