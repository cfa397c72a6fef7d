"""95th percentile of event-to-commit latency over all events committed in
the window (every event, not a mean of chunks).  Serves the end-to-end
``event_p95_ms`` and the per-layer ``event_p95_ms.overload``."""
import numpy as np


def read(run):
    lat = run.latencies_ms
    return float(np.percentile(lat, 95)) if len(lat) else None
