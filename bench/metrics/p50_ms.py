"""Median latency of every op due in the window, each timed from its due
time to the return of the call that carried it."""
import numpy as np


def read(run):
    lat = run.latency_s
    return None if np.isnan(lat).any() or not len(lat) else \
        1e3 * float(np.percentile(lat, 50))
