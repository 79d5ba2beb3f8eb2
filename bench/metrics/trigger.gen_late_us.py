"""99th percentile of how late the detector feed delivered frames after
their due time, in µs (host clock): a starved producer, not a slow trigger."""

import numpy as np


def read(view):
    late = view.records.get("gen_late_s")
    return 1e6 * float(np.percentile(late, 99)) if late is not None \
        and len(late) else None
