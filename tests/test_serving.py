"""Serving engines.

LM engine: continuous batching, lane reuse, recurrent-state reset.
Design engine: adaptive batching, warm-boot artifacts, fault-tolerant
replica restarts (the save/load + fault-injection acceptance criteria).
"""

import time

import jax
import numpy as np
import pytest

import repro.hls as hls
from repro.configs import registry
from repro.models import braggnn
from repro.nn import module, transformer
from repro.runtime.fault import FailureInjector
from repro.serving.design_engine import DesignEngine, default_buckets
from repro.serving.engine import ServingEngine


def _engine(arch="qwen2.5-3b", max_batch=3, max_len=64):
    cfg = registry.get_tiny(arch)
    params = module.init_tree(transformer.model_specs(cfg),
                              jax.random.key(0))
    return ServingEngine(cfg, params, max_batch=max_batch, max_len=max_len)


def test_continuous_batching_drains_more_requests_than_lanes():
    eng = _engine(max_batch=2)
    rids = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(5)]
    finished = eng.run_until_drained()
    assert len(finished) == 5
    assert sorted(r.rid for r in finished) == rids
    for r in finished:
        assert len(r.output) == 4
    s = eng.stats()
    assert s["generated_tokens"] == 20


def test_deterministic_outputs_independent_of_batching():
    """A request's tokens must not depend on lane traffic around it."""
    eng1 = _engine(max_batch=1)
    eng1.submit([5, 6, 7, 8], max_new_tokens=6)
    alone = eng1.run_until_drained()[0].output

    eng2 = _engine(max_batch=3)
    eng2.submit([9, 10], max_new_tokens=6)
    eng2.submit([5, 6, 7, 8], max_new_tokens=6)
    eng2.submit([11, 12, 13], max_new_tokens=6)
    packed = {r.rid: r.output for r in eng2.run_until_drained()}
    assert packed[1] == alone


@pytest.mark.parametrize("arch", ["xlstm-1.3b", "recurrentgemma-9b"])
def test_lane_reuse_resets_recurrent_state(arch):
    """Recurrent state must not leak between requests sharing a lane."""
    eng = _engine(arch, max_batch=1, max_len=48)
    eng.submit([3, 4, 5], max_new_tokens=5)
    first = eng.run_until_drained()[-1].output

    # same prompt again through the SAME lane after other traffic
    eng.submit([20, 21, 22, 23, 24, 25], max_new_tokens=5)
    eng.run_until_drained()
    eng.submit([3, 4, 5], max_new_tokens=5)
    again = eng.run_until_drained()[-1].output
    assert again == first


def test_eos_stops_generation():
    eng = _engine(max_batch=1)
    # pick eos as whatever the model emits first so it stops at length 1
    eng.submit([1, 2], max_new_tokens=8)
    tok = eng.run_until_drained()[0].output[0]
    eng2 = _engine(max_batch=1)
    eng2.submit([1, 2], max_new_tokens=8, eos_id=tok)
    out = eng2.run_until_drained()[0].output
    assert out[0] == tok and len(out) == 1


# ---------------------------------------------------------------------------
# DesignEngine: adaptive batching over a compiled Design
# ---------------------------------------------------------------------------


IMG = 7


@pytest.fixture(scope="module")
def bound_design():
    model = braggnn.build(1, IMG)
    params = model.init_params(jax.random.key(0))
    return hls.Session().compile(model.bind(params), name="braggnn_engine")


@pytest.fixture(scope="module")
def samples():
    rng = np.random.default_rng(0)
    return [rng.normal(0.0, 0.25, (1, 1, IMG, IMG)).astype(np.float32)
            for _ in range(9)]


def _drain(engine, xs):
    reqs = [engine.submit(x) for x in xs]
    engine.run_until_drained()
    return [r.wait(timeout=30) for r in reqs]


def _assert_same(a, b):
    """Bit-identity across output memref dicts."""
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_default_buckets():
    assert default_buckets(8) == (1, 2, 4, 8)
    assert default_buckets(12) == (1, 2, 4, 8, 12)
    assert default_buckets(1) == (1,)
    with pytest.raises(ValueError):
        default_buckets(0)


def test_engine_sync_mode_serves_all_requests(bound_design, samples):
    eng = bound_design.engine(max_batch=4)
    outs = _drain(eng, samples)
    rep = eng.report()
    assert rep.completed == len(samples) and rep.dropped == 0
    assert all(np.asarray(o["dense_3_out"]).shape == (1, 2) for o in outs)
    # head-of-queue grouping: 9 requests, max_batch 4 -> 4+4+1
    assert sorted(rep.batch_hist.items()) == [(1, 1), (4, 2)]
    assert rep.p95_ms >= rep.p50_ms >= 0.0


def test_engine_matches_design_serve(bound_design, samples):
    """Engine per-sample outputs == the sync Design.serve outputs.

    Same bucket shape as the serve batch — bit-identity is a per-compiled-
    program property, so the comparison pins one (9,) dispatch.
    """
    eng = bound_design.engine(buckets=(len(samples),))
    outs = _drain(eng, samples)
    batch = np.concatenate(samples)          # (9, 1, IMG, IMG)
    report = bound_design.serve([batch], collect=True)
    ref = report.outputs[0]
    for i, o in enumerate(outs):
        _assert_same(o, {k: np.asarray(v)[i] for k, v in ref.items()})


def test_engine_padding_counts_bucket_fill(bound_design, samples):
    eng = bound_design.engine(buckets=(4,))
    _drain(eng, samples[:3])
    rep = eng.report()
    assert rep.batch_hist == {4: 1}
    assert rep.padded_samples == 1


def test_engine_threaded_mode_drains_on_stop(bound_design, samples):
    eng = bound_design.engine(max_batch=4, max_delay_ms=1.0)
    with eng:
        reqs = [eng.submit(x) for x in samples]
        outs = [r.wait(timeout=30) for r in reqs]
    rep = eng.report()
    assert rep.completed == len(samples) and rep.dropped == 0
    assert rep.qps > 0
    # the design returns its output memrefs as a dict, sliced per sample
    assert all(np.asarray(o["dense_3_out"]).shape == (1, 2) for o in outs)
    with pytest.raises(RuntimeError, match="stopped"):
        eng.submit(samples[0])


def test_engine_rejects_bad_sample_shape(bound_design):
    eng = bound_design.engine(max_batch=2)
    with pytest.raises(ValueError, match="does not match input memref"):
        eng.submit(np.zeros((3, 3), np.float32))


# ---------------------------------------------------------------------------
# Warm-boot artifacts: Design.save / hls.load
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", [None, "5_4"])
def test_save_load_round_trip_bit_identical(bound_design, samples,
                                            tmp_path, fmt):
    path = tmp_path / "bragg.design"
    bound_design.save(path, fmt=fmt)
    ref = _drain(bound_design.engine(fmt=fmt, max_batch=4), samples)

    loaded = hls.load(path)
    assert loaded.manifest["fmt"] == fmt
    assert loaded.manifest["path"] == str(path)
    eng = loaded.engine(max_batch=4)         # fmt from the manifest
    assert eng.fmt == fmt
    outs = _drain(eng, samples)
    for a, b in zip(ref, outs):
        _assert_same(a, b)


def test_load_rejects_non_artifact(tmp_path):
    p = tmp_path / "junk.design"
    import pickle
    p.write_bytes(pickle.dumps({"nope": 1}))
    with pytest.raises(ValueError, match="not a repro design artifact"):
        hls.load(p)
    with pytest.raises(FileNotFoundError):
        hls.load(tmp_path / "missing.design")


# ---------------------------------------------------------------------------
# Fault tolerance: poisoned dispatch -> artifact warm re-boot, zero dropped
# ---------------------------------------------------------------------------


def test_fault_injection_restarts_from_artifact_no_request_lost(
        bound_design, samples, tmp_path):
    path = tmp_path / "bragg.design"
    bound_design.save(path)

    # uninterrupted reference run
    ref = _drain(bound_design.engine(max_batch=4,
                                     artifact_path=path), samples)

    # poison dispatch 1: the second batch fails mid-stream
    inj = FailureInjector(fail_at=(1,))
    eng = bound_design.engine(max_batch=4,
                              artifact_path=path, injector=inj)
    outs = _drain(eng, samples)
    rep = eng.report()
    assert inj.fired == [1]
    assert rep.restarts == 1
    assert rep.boots == ["memory", "artifact"]   # re-booted from the file
    assert rep.dropped == 0
    assert rep.retried == 4                      # the failed batch, requeued
    assert rep.completed == len(samples)
    for a, b in zip(ref, outs):                  # bit-identical recovery
        _assert_same(a, b)


def test_fault_exhausted_retries_fail_requests_not_hang(bound_design,
                                                        samples):
    inj = FailureInjector(fail_at=(0, 1, 2))
    eng = bound_design.engine(max_batch=4, max_retries=2,
                              injector=inj)
    reqs = [eng.submit(x) for x in samples[:4]]
    eng.run_until_drained()
    rep = eng.report()
    assert rep.restarts == 3
    assert rep.dropped == 4                      # failed after max_retries
    for r in reqs:
        with pytest.raises(RuntimeError, match="injected failure"):
            r.wait(timeout=5)


# ---------------------------------------------------------------------------
# ServeReport percentiles (sync Design.serve gains the same tail fields)
# ---------------------------------------------------------------------------


def test_serve_report_has_percentiles(bound_design, samples):
    batch = np.concatenate(samples)
    report = bound_design.serve([batch] * 5)
    assert report.p99_ms >= report.p95_ms >= report.p50_ms > 0.0
    assert "p50" in report.summary()


# ---------------------------------------------------------------------------
# percentiles() edge cases
# ---------------------------------------------------------------------------


def test_percentiles_empty_returns_zeros():
    from repro.serving.common import percentiles
    assert percentiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0}


def test_percentiles_single_sample_all_equal():
    from repro.serving.common import percentiles
    assert percentiles([7.5]) == {"p50": 7.5, "p95": 7.5, "p99": 7.5}


def test_percentiles_filters_nan():
    from repro.serving.common import percentiles
    p = percentiles([1.0, float("nan"), 3.0])
    assert p["p50"] == 2.0                        # nan dropped, not sorted-in
    assert np.isfinite(p["p95"]) and np.isfinite(p["p99"])
    # all-NaN degrades like empty rather than propagating NaN
    assert percentiles([float("nan")] * 4) == \
        {"p50": 0.0, "p95": 0.0, "p99": 0.0}


# ---------------------------------------------------------------------------
# queue-depth telemetry (time-weighted, not sampled-at-dispatch-only)
# ---------------------------------------------------------------------------


def test_queue_depth_counts_idle_and_ramp_periods(bound_design, samples):
    """A burst of 8 queued requests must report a max depth of 8 and a
    time-weighted mean/p95 near the top, even though dispatch-time
    sampling alone would see the queue only as it drains (mean ~4)."""
    eng = bound_design.engine(buckets=(1,))
    for x in samples[:8]:
        eng.submit(x)
    time.sleep(0.25)          # the queue sits at depth 8 the whole time
    eng.run_until_drained()
    rep = eng.report()
    assert rep.completed == 8
    assert rep.max_queue_depth == 8
    # the dwell at depth 8 dominates the drain transitions
    assert rep.p95_queue_depth >= 7
    assert rep.mean_queue_depth > 5


def test_queue_depth_stats_unit():
    from repro.serving.common import RequestQueue
    q = RequestQueue()
    # hand-build a step function: depth 2 for 1s, depth 10 for 9s
    q.depth_events = [(0.0, 2), (1.0, 10), (10.0, 0)]
    stats = q.depth_stats()
    assert stats["max"] == 10.0
    assert stats["mean"] == pytest.approx(0.1 * 2 + 0.9 * 10)
    assert stats["p95"] == 10.0
