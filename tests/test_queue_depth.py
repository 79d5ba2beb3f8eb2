"""RequestQueue's depth telemetry, kept as running state, reads what a
full ``(t, depth)`` log reads.

The reference keeps every observation and integrates the step function
afterwards; the queue folds each observation in as it comes.  Both sum in
the same order, so the numbers agree exactly.
"""

import numpy as np
import pytest

from repro.serving.common import RequestQueue


def _reference(events):
    """``(max_depth, mean_depth, depth_stats())`` from the whole log."""
    vals = [d for _, d in events]
    max_depth = max(vals, default=0)
    mean_depth = float(np.mean(vals)) if vals else 0.0
    if not events:
        return max_depth, mean_depth, {"max": 0, "mean": 0.0, "p95": 0.0}
    if len(events) == 1:
        d = float(events[0][1])
        return max_depth, mean_depth, {"max": int(d), "mean": d, "p95": d}
    total = events[-1][0] - events[0][0]
    if total <= 0:
        return max_depth, mean_depth, {
            "max": max(vals), "mean": float(np.mean(vals)),
            "p95": float(np.percentile(vals, 95))}
    weight = {}
    for (t0, d), (t1, _) in zip(events, events[1:]):
        weight[d] = weight.get(d, 0.0) + (t1 - t0)
    mean = sum(d * w for d, w in weight.items()) / total
    p95 = float(max(weight))
    acc = 0.0
    for d in sorted(weight):
        acc += weight[d]
        if acc >= 0.95 * total:
            p95 = float(d)
            break
    return max_depth, mean_depth, {"max": max(vals), "mean": mean,
                                   "p95": p95}


def _log(seed, n, same_instant=False):
    rng = np.random.default_rng(seed)
    t = 0.0 if same_instant else np.cumsum(rng.exponential(1e-3, n)) + 5.0
    depths = np.abs(np.cumsum(rng.integers(-2, 3, n)))
    return [(float(t if same_instant else t[i]), int(depths[i]))
            for i in range(n)]


@pytest.mark.parametrize("events", [[], [(1.0, 3)], _log(0, 7, True),
                                    _log(1, 50), _log(2, 2000)],
                         ids=["empty", "one", "one_instant", "short", "long"])
def test_running_state_matches_the_whole_log(events):
    q = RequestQueue()
    q.depth_events = events
    max_depth, mean_depth, stats = _reference(events)
    assert q.max_depth == max_depth
    assert q.mean_depth == mean_depth
    assert q.depth_stats() == stats


def test_live_queue_folds_each_push_and_pop():
    q = RequestQueue()
    for i in range(5):
        q.submit(i)
    q.pop_batch(2)
    q.sample_depth()
    q.pop_batch(3)
    assert q.max_depth == 5
    # depths observed: 1 2 3 4 5 (pushes), 3 (pop), 3 (timer), 0 (pop)
    assert q.mean_depth == pytest.approx(21 / 8)
    assert q.depth_stats()["max"] == 5
