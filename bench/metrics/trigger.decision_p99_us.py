"""99th percentile of the traced window's decision latencies, each from the
frame's due time, in µs (host clock): the tail that the chip machine's
whole-host stalls of about 110 ms set whenever one falls in the window."""

import numpy as np


def read(view):
    lat = view.records.get("decision_latency_s")
    return 1e6 * float(np.percentile(lat, 99)) if lat is not None \
        and len(lat) else None
