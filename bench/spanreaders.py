"""Per-layer readers of the program's own spans in a traced run.

While the profiler captures, the program's ``repro.obs`` spans are
profiler annotations under their bare names, on the device trace's clock:
``nest.call`` around one call of the nest tier, ``nest.feeds`` (merging
the bound weights into the feed dict), ``nest.weights`` (each weight feed
to a float32 array, unbatched), ``nest.launch`` (the jitted call: dispatch
and the copy of its arguments to the device), and ``trigger.wait`` (the
trigger's wait for a window of frames).  Each reader returns ``None`` when
the run holds none of the spans it reads.
"""

from __future__ import annotations

import numpy as np


def _spans(view, name: str) -> list:
    return view.trace.spans(name) if view.trace is not None else []


def mean_us(view, name: str):
    """Mean length of the window's ``name`` spans, in µs."""
    spans = _spans(view, name)
    if not spans:
        return None
    return 1e-3 * float(np.mean([h[2] for h in spans]))


def prep_us_per_call(view):
    """``nest.feeds`` and ``nest.weights`` time summed over the window, per
    ``nest.call`` span, in µs: the host's work before the launch."""
    calls = _spans(view, "nest.call")
    prep = _spans(view, "nest.feeds") + _spans(view, "nest.weights")
    if not calls or not prep:
        return None
    return 1e-3 * sum(h[2] for h in prep) / len(calls)


def idle_in_calls_pct(view):
    """The share of the window in which the device idles inside a
    ``nest.call`` span, in %: each span less the device-busy time inside
    it, summed, over the window."""
    calls = _spans(view, "nest.call")
    tr = view.trace
    if not calls or tr.window_s() <= 0:
        return None
    idle = sum(h[2] * 1e-9 - tr.busy_within(h[1], h[1] + h[2])
               for h in calls)
    return 100.0 * idle / tr.window_s()
