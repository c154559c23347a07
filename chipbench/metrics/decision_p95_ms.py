"""95th percentile of the wall time of every ``run_round`` call in the
window, all rounds pooled."""
import numpy as np


def read(run):
    return float(np.percentile([r.wall_s for r in run.rounds], 95)) * 1e3
