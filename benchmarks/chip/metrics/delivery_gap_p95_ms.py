"""p95 over every delivery of the window of the gap since the same
request's previous delivery (a delivery: the tokens of one request that
one step() makes visible)."""
import numpy as np


def read(run):
    if not run.gaps_s:
        return None
    return float(np.percentile(run.gaps_s, 95)) * 1e3
