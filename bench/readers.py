"""Arithmetic shared by the per-layer readers in ``bench/metrics``.

Each reader returns ``None`` when its run holds nothing to read, and the
harness then leaves the metric out; a share of a roofline or a peak is
never reported as 0 for want of a reading.
"""

from __future__ import annotations

import numpy as np


def idle_pct(view):
    tr = view.trace
    if tr is None or tr.window_s() <= 0 or not tr.ops:
        return None
    return 100.0 * tr.idle_share()


def host_us_per_call(view):
    """Mean over the window's ``bench.call`` spans of the span's length
    less the device-busy time inside it, in µs."""
    tr = view.trace
    calls = tr.spans("bench.call") if tr is not None else []
    if not calls:
        return None
    per = [h[2] * 1e-9 - tr.busy_within(h[1], h[1] + h[2]) for h in calls]
    return 1e6 * float(np.mean(per))


def kernel_roofline_pct(view, kernel: str):
    """Least time over measured time of one Pallas kernel, in %.

    The least time of a call is the larger of its FLOPs over the peak and
    its bytes over the memory bandwidth, each from the layer's logical
    shapes (``model.layers``) at the call's batch.  The window's calls are
    counted from the kernel's events, one per layer it computes."""
    tr = view.trace
    if tr is None or view.peaks is None or "batch" not in view.records:
        return None
    events = tr.kernel_events(kernel)
    layers = [ly for ly in view.model.layers(view.cfg)
              if ly["kernel"] == kernel]
    if not events or not layers or len(events) % len(layers):
        return None
    batch, peaks = view.records["batch"], view.peaks
    least = sum(max(2.0 * batch * ly["macs"] / peaks["flops_per_s"],
                    (batch * ly["act_bytes"] + ly["weight_bytes"])
                    / peaks["hbm_bytes_per_s"]) for ly in layers)
    measured = sum(e[4] for e in events) * 1e-9
    return 100.0 * least * (len(events) // len(layers)) / measured


def mfu_pct(view):
    """Model FLOPs of the samples the traced window completed, per second
    of that window, over the chip's peak, in %."""
    r = view.records
    if view.peaks is None or not r.get("window_s") or not r.get("samples"):
        return None
    rate = r["samples"] / r["window_s"]
    return 100.0 * view.model.model_flops(view.cfg) * rate \
        / view.peaks["flops_per_s"]
