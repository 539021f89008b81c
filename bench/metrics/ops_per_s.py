"""Ops completed inside the traffic window over the window's seconds."""
import numpy as np


def read(run):
    return float(np.sum(run.done <= run.traffic_s)) / run.traffic_s
