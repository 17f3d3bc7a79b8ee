"""95th percentile of the time a request spends outside the index call
that served it: latency from its due time less its batch's service time."""

import numpy as np


def read(record):
    lat = record.get("latency_s")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat - record["service_s"], 95)) * 1e3
