"""95th percentile of request latency in the traced window (``ms``).

Every request due in the window, from its due time on the open-loop
schedule to its future's resolution, as the untraced run's ``p50_ms``
times it. A host stall of a second moves it by as much, so it is read
here, beside the steadier median, and not held to a bound.
"""
import numpy as np


def read(record):
    lat = record.get("latencies_ms")
    return float(np.percentile(lat, 95)) if lat else None
