"""95th percentile of a request's wait in the engine's queue (its dispatch
start less its submit time), in ms."""

import numpy as np


def read(view):
    q = view.records.get("queued_ms")
    return float(np.percentile(q, 95)) if q is not None and len(q) else None
