"""95th percentile of every answered request's latency, from the time it
was due to be sent to the time its answer was on the host, in ms."""
import numpy as np


def read(rec):
    lat = rec["window"].get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(lat, 95)) * 1e3
