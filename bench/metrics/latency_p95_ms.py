"""95th percentile of the batch latency over every batch completed in the
window, the sample ``latency_p50_ms`` takes (host clock)."""
import numpy as np


def read(run):
    lat = [r.done - r.due for r in run.completed]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
