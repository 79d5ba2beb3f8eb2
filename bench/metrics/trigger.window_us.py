"""Mean length of the program's `trigger.window` spans in the window, in
µs: one window of frames through the design, predicate and bookkeeping."""

import numpy as np


def read(view):
    durs = [d for name, d in view.records.get("obs_spans", [])
            if name == "trigger.window"]
    return 1e6 * float(np.mean(durs)) if durs else None
