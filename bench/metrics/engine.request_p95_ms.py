"""95th percentile of the traced window's request latencies, each from the
request's due time, in ms (host clock): the tail that the chip machine's
whole-host stalls of about 110 ms set whenever one falls in the window."""

import numpy as np


def read(view):
    lat = view.records.get("latency_ms")
    return float(np.percentile(lat, 95)) if lat is not None and len(lat) \
        else None
