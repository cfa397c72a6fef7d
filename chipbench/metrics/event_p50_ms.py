"""Median event-to-commit latency over all events committed in the window:
from each event's due time to the return of the ``apply_wave`` that
committed it."""
import numpy as np


def read(run):
    lat = run.latencies_ms
    return float(np.percentile(lat, 50)) if len(lat) else None
