"""Median batch latency over every batch completed in the window: from the
batch's hand-over to ``process`` to its egress being ready (host clock)."""
import numpy as np


def read(run):
    lat = [r.done - r.due for r in run.completed]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
