"""The nest tier's resident weights: a bound module's weights go to the
device once per lowering, and a call copies only its input.

Weights in a call's feeds still win over the resident ones, so every answer
is checked against ``emit.evaluate`` over the weights the call used.  The
counters ``nest.weight_uploads``, ``nest.calls_resident`` and
``nest.calls_fed`` say which path each call took.
"""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.hls as hls
from repro import obs, trigger
from repro.core import emit
from repro.models import braggnn

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

IMG = 9


@pytest.fixture(autouse=True)
def _obs_on():
    """Every test counts from zero with recording on."""
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def module():
    m = braggnn.build(1, img=IMG)
    return m.bind(m.init_params(jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def design(module):
    return hls.compile(module)


@pytest.fixture(scope="module")
def x():
    rng = np.random.default_rng(0)
    return (rng.standard_normal((4, 1, 1, IMG, IMG)) * 0.2).astype(np.float32)


def _count(name: str) -> float:
    return obs.snapshot()["counters"].get(name, 0.0)


def _assert_matches(out, ref):
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fmt", [None, "5_4"], ids=["fp32", "5_4"])
def test_resident_path_bit_identical_to_fed_path(design, x, fmt):
    fn = design.jax_fn(fmt=fmt)
    assert _count("nest.weight_uploads") == 1
    resident = fn({"input": x})
    fed = fn(design.feeds(x))
    assert _count("nest.calls_resident") == 1
    assert _count("nest.calls_fed") == 1
    assert set(resident) == set(fed)
    for k in fed:
        np.testing.assert_array_equal(np.asarray(resident[k]),
                                      np.asarray(fed[k]))


def test_serve_uploads_once_and_every_call_is_resident(design, x):
    n = 5
    rep = design.serve([x] * n, collect=True)
    assert rep.batches == n
    # the warm-up call, then every batch
    assert _count("nest.weight_uploads") == 1
    assert _count("nest.calls_resident") == n + 1
    assert _count("nest.calls_fed") == 0
    ref = emit.evaluate(design.graph_opt, design.feeds(x))
    _assert_matches(rep.outputs[-1], ref)


def test_trigger_uploads_once_and_every_window_is_resident(design):
    loop = design.trigger(window=4, threshold=0.0)
    assert _count("nest.weight_uploads") == 1
    assert _count("nest.calls_resident") == 1            # warm-up
    rep = loop.run(trigger.DetectorFeed(img=IMG, seed=3), 10)
    assert rep.processed == 10 and rep.windows == 3      # 4 + 4 + padded 2
    assert _count("nest.weight_uploads") == 1
    assert _count("nest.calls_resident") == 1 + rep.windows
    assert _count("nest.calls_fed") == 0


def test_engine_warms_every_bucket_on_the_resident_path(design, x):
    eng = design.engine(max_batch=4)
    buckets = len(eng.buckets)
    assert _count("nest.weight_uploads") == 1
    assert _count("nest.calls_resident") == buckets
    reqs = [eng.submit(s) for s in x[:3]]
    eng.run_until_drained()
    assert _count("nest.calls_fed") == 0
    assert _count("nest.calls_resident") > buckets
    ref = emit.evaluate(design.graph_opt, design.feeds(x))
    for i, r in enumerate(reqs):
        for k in ref:
            np.testing.assert_allclose(np.asarray(r.wait(30)[k]), ref[k][i],
                                       rtol=1e-4, atol=1e-5)


def test_fed_weights_of_another_param_set_win(design, module, x):
    other = module.weight_feeds(module.init_params(jax.random.PRNGKey(1)))
    feeds = {"input": x, **other}
    fn = design.jax_fn()
    out = fn(feeds)
    assert _count("nest.calls_fed") == 1
    assert _count("nest.calls_resident") == 0
    _assert_matches(out, emit.evaluate(design.graph_opt, feeds))
    own = fn({"input": x})
    assert any(not np.allclose(np.asarray(own[k]), np.asarray(out[k]))
               for k in out), "the fed weights must change the answer"


def test_one_fed_weight_joins_the_resident_rest(design, module, x):
    other = module.weight_feeds(module.init_params(jax.random.PRNGKey(1)))
    name = sorted(other)[0]
    fn = design.jax_fn()
    out = fn({"input": x, name: other[name]})
    assert _count("nest.calls_fed") == 1
    merged = dict(design.feeds(x))
    merged[name] = other[name]
    _assert_matches(out, emit.evaluate(design.graph_opt, merged))


def test_per_sample_weights_still_rejected(design, module, x):
    name, w = sorted(module.weight_feeds().items())[0]
    varied = np.stack([w + i for i in range(len(x))]).astype(np.float32)
    fn = design.jax_fn()
    with pytest.raises(ValueError, match="varies across the batch"):
        fn({"input": x, name: varied})


def test_unbound_module_still_needs_weight_feeds(x):
    design = hls.compile(braggnn.build(1, img=IMG))
    fn = design.jax_fn()
    assert _count("nest.weight_uploads") == 0
    with pytest.raises(KeyError, match="missing weight feeds"):
        fn({"input": x})


def test_annotated_calls_keep_one_feed_dict(design, x):
    """A traced benchmark run wraps every Pallas callable in a
    ``call(feeds)`` that exposes only ``.plan``: serving and the engine
    must answer through it unchanged."""
    from bench import devtrace
    devtrace.annotate_calls(design)
    try:
        ref = emit.evaluate(design.graph_opt, design.feeds(x))
        rep = design.serve([x, x], collect=True)
        _assert_matches(rep.outputs[-1], ref)
        eng = design.engine(max_batch=4)
        reqs = [eng.submit(s) for s in x]
        eng.run_until_drained()
        for i, r in enumerate(reqs):
            for k in ref:
                np.testing.assert_allclose(np.asarray(r.wait(30)[k]),
                                           ref[k][i], rtol=1e-4, atol=1e-5)
        assert _count("nest.calls_fed") == 0
        assert _count("nest.weight_uploads") == 2     # serve, engine
    finally:
        del design.jax_fn
