"""The trigger entry: frames on the detector's clock through ``TriggerLoop``.

The loop is ``design.trigger(backend=..., window=...)`` with a predicate
through the public ``predicate=`` hook, which keeps every window's outputs
for the comparison and applies the threshold rule of the stock predicate.
The threshold is the median score over the first calibration frames, as a
deployment calibrates on beam data.  The window is one
``TriggerLoop.run(feed, n, realtime=True)`` over ``frame_rate_hz *
seconds`` frames; each decision is timed from its frame's due time (the
producer's start plus ``t_sched``), so a late producer counts.
"""

from __future__ import annotations

import time

import numpy as np

from bench.feed import DetectorFeed


class RecordingPredicate:
    """Accept when any output magnitude reaches ``threshold``; keep each
    window's outputs and scores, in the order the windows ran.

    The score it hands back for a row is the row's tag, ``window * w +
    row`` for window ``w`` of ``window`` rows, so that every decision the
    loop records names the output row it was made from (real scores repeat
    where outputs saturate, and a padded row's can equal a real one's)."""

    def __init__(self, window: int):
        self.window = window
        self.threshold = float("inf")
        self.windows: list[tuple[np.ndarray, np.ndarray]] = []

    def __call__(self, outputs):
        (out,) = outputs.values()
        arr = np.array(out, dtype=np.float32).reshape(len(out), -1)
        scores = np.abs(arr).max(axis=1)
        tags = len(self.windows) * self.window + np.arange(len(arr))
        self.windows.append((arr, scores))
        return scores >= self.threshold, tags.astype(np.float64)


def match_windows(decisions, windows, window: int) -> tuple[list, int]:
    """Pair each decision with the output row and score it came from, by
    the row's tag.  Returns ``[(decision, row, score)]`` and the number of
    decisions whose tag names no row, or a row another decision took."""
    pairs, seen, unmatched = [], set(), 0
    for d in decisions:
        w, k = divmod(int(d.score), window)
        if (d.score != int(d.score) or not 0 <= w < len(windows)
                or d.score in seen):
            unmatched += 1
            continue
        seen.add(d.score)
        arr, scores = windows[w]
        pairs.append((d, arr[k], float(scores[k])))
    return pairs, unmatched


def run(ctx, design) -> dict:
    tr = ctx.traffic
    rate = float(tr["frame_rate_hz"])
    t0 = time.perf_counter()
    feed = DetectorFeed(img=ctx.cfg["img"], frame_rate_hz=rate,
                        seed=ctx.seed, **tr["feed"])
    pool = feed.render(tr["pool_frames"])
    ctx.part("render_frames", t0)

    t0 = time.perf_counter()
    pred = RecordingPredicate(tr["window"])
    loop = design.trigger(backend=ctx.backend, fmt=ctx.fmt_key,
                          window=tr["window"], predicate=pred)
    ctx.part("first_call_compile", t0)
    t0 = time.perf_counter()
    loop.warmup()
    loop.run(feed, tr["calibration_frames"])
    pred.threshold = float(np.median(np.concatenate(
        [s for _, s in pred.windows])))
    pred.windows.clear()
    ctx.part("warm_up", t0)

    n = int(round(rate * ctx.seconds))
    ctx.window_start()
    rep = loop.run(feed, n, realtime=True)
    ctx.window_end()

    frames = feed.handed_out
    pairs, unmatched = match_windows(rep.decisions, pred.windows,
                                     tr["window"])
    lat, late = [], []
    for d, _, _ in pairs:
        f = frames[d.frame_id]
        due = feed.t_start + f.t_sched
        lat.append(f.arrival_t + d.latency_us * 1e-6 - due)
        late.append(f.arrival_t - due)
    lat_us = np.asarray(lat) * 1e6
    ids = np.array([d.frame_id for d, _, _ in pairs], dtype=np.int64)
    outputs = (np.stack([row for _, row, _ in pairs]) if pairs
               else np.zeros((0, 2), np.float32))
    thr = pred.threshold

    def exact(ref_rows):
        ref = ref_rows[ids % len(pool)]
        ref_score = np.abs(ref).reshape(len(ref), -1).max(axis=1)
        got_score = np.array([sc for _, _, sc in pairs], dtype=np.float32)
        accepts = np.array([d.accept for d, _, _ in pairs], dtype=bool)
        # a decision must follow the threshold applied to its own output,
        # and agree with the reference's unless the two outputs straddle it
        wrong_own = np.count_nonzero(accepts != (got_score >= thr))
        straddle = (np.minimum(ref_score, got_score) < thr) \
            & (np.maximum(ref_score, got_score) >= thr)
        wrong_ref = np.count_nonzero((accepts != (ref_score >= thr))
                                     & ~straddle)
        return {"decisions_wrong": wrong_own + wrong_ref,
                "unmatched": unmatched}

    return {
        "attempted": n,
        "failed": n - len(pairs),
        "e2e": {"decision_p50_us": float(np.percentile(lat_us, 50))
                if len(lat_us) else float("inf")},
        "inputs": pool,
        "input_index": ids % len(pool),
        "outputs": outputs,
        "missing": 0,
        "exact": exact,
        "state": loop,
        "records": {"gen_late_s": np.asarray(late),
                    "decision_latency_s": lat_us * 1e-6,
                    "calls": rep.windows, "accepts": rep.accepts,
                    "dropped": rep.dropped},
    }
