"""The public API surface: the ``repro.hls`` contract.

Covers the documented ``__all__`` surface and bit-identity
(CompiledDesign hash) of ``hls.compile`` with the direct
``CompilerDriver`` path.
"""

import numpy as np
import pytest

import repro.hls as hls
from repro.core import frontend, verify
from repro.core.pipeline import CompilerConfig, CompilerDriver

#: The documented surface (README "Public API" section).  Additions are
#: deliberate API changes: update the README and this tuple together.
DOCUMENTED_SURFACE = (
    "compile",
    "load",
    "trace",
    "Design",
    "Session",
    "ServeReport",
    "CompilerConfig",
    "CompiledDesign",
    "ModuleGraph",
)


def conv_build(ctx):
    x = ctx.memref("input", (1, 3, 8, 8), "input")
    w = ctx.memref("weight", (4, 3, 3, 3), "weight")
    b = ctx.memref("bias", (4,), "weight")
    out = ctx.memref("out", (1, 4, 6, 6), "output")
    frontend.conv2d(ctx, x, w, b, out)


@pytest.fixture()
def design():
    return hls.Session().compile(conv_build, name="conv_api")


# ---------------------------------------------------------------------------
# Surface
# ---------------------------------------------------------------------------


def test_all_is_the_documented_surface():
    assert tuple(hls.__all__) == DOCUMENTED_SURFACE
    for name in hls.__all__:
        assert getattr(hls, name, None) is not None, name


def test_compile_rejects_garbage():
    with pytest.raises(TypeError, match="ModuleGraph"):
        hls.compile(42)


# ---------------------------------------------------------------------------
# Bit-identity with the internal driver
# ---------------------------------------------------------------------------


def test_hash_identical_to_compiler_driver(design):
    direct = CompilerDriver().compile(conv_build, name="conv_api")
    assert design.design_hash == direct.design_hash
    assert design.graph_opt is not direct.graph_opt  # separate caches
    np.testing.assert_array_equal(design.graph_opt.cols().opcode,
                                  direct.graph_opt.cols().opcode)


def test_trace_matches_driver_trace():
    from repro.core.pipeline import graph_fingerprint
    g = hls.trace(conv_build)
    g2 = CompilerDriver().trace(conv_build)
    assert graph_fingerprint(g) == graph_fingerprint(g2)


# ---------------------------------------------------------------------------
# Design verbs
# ---------------------------------------------------------------------------


def test_run_accepts_dict_and_merges_nothing_for_plain_builds(design):
    feeds = verify.random_feeds(design.graph_raw, batch=3, seed=0)
    out = design.run(feeds)
    assert out["out"].shape == (3, 1, 4, 6, 6)
    # matches the raw artifact evaluation
    ref = design.compiled.evaluate(feeds)
    np.testing.assert_array_equal(out["out"], ref["out"])


def test_verify_passes(design):
    rep = design.verify(batch=2, seed=0)
    assert rep.passed, rep.summary()


def test_with_config_shares_trace_and_changes_hash(design):
    d2 = design.with_config(CompilerConfig(pipeline=("cse", "dce")))
    assert d2.design_hash != design.design_hash
    assert d2.graph_raw is design.graph_raw        # trace shared
    assert d2.session is design.session


def test_report_mentions_pipeline_and_schedule(design):
    text = design.report()
    assert "pipeline" in text and "schedule" in text
    assert design.design_hash[:12] in text


def test_session_cache_hit():
    s = hls.Session()
    d1 = s.compile(conv_build, name="a")
    d2 = s.compile(conv_build, name="a")
    assert s.stats()["hits"] == 1
    assert d1.compiled is d2.compiled


def test_serve_simd_backend(design):
    """A loop nest serves through the compiled rendering's DFG tier."""
    x = np.random.default_rng(0).normal(
        0, 0.5, (4, 3, 8, 8)).astype(np.float32)
    weights = verify.random_feeds(design.graph_raw, batch=1, seed=1)
    feeds = {k: v[0] for k, v in weights.items() if k != "input"}
    feeds["input"] = x[:, None]
    rep = design.serve([feeds, feeds], collect=True)
    assert rep.served.startswith("pallas[dfg]")
    assert rep.batches == 2 and rep.samples == 8
    assert rep.us_per_sample > 0
    assert len(rep.outputs) == 2


def test_example_inputs_shape_checked():
    with pytest.raises(ValueError, match="does not match"):
        hls.Session().compile(conv_build, example_inputs=np.zeros((4, 7, 7)))


# ---------------------------------------------------------------------------
# Tuning verbs (the resolve_config replacement)
# ---------------------------------------------------------------------------


def test_tune_persists_and_apply_tuned_loads(design, tmp_path, caplog):
    import logging
    from repro.tune import TuningDB, conv2d_space
    db = TuningDB(tmp_path / "db.json")
    space = conv2d_space()

    # miss path is loud, not silent: names the probed DB path (a WARNING
    # on the repro logger since the print->logging conversion)
    with caplog.at_level(logging.WARNING, logger="repro"):
        same, cand = design.apply_tuned(space, db=db)
    assert same is design and cand is None
    assert str(db.path) in caplog.text
    caplog.clear()

    result = design.tune(space, strategy="random", budget=2, db=db, dry=True)
    assert len(result.trials) >= 1 and len(db) == 1   # auto-persisted

    tuned, cand = design.apply_tuned(space, db=db)
    assert cand is not None
    assert tuned.config == space.to_config(cand)
    assert tuned.tuned_candidate is cand
    # a covered rerun is served from the DB without searching
    again = design.tune(space, strategy="random", budget=2, db=db, dry=True)
    assert again.from_db

    # compile(tuned=space) resolves the win before its single compile
    d3 = hls.compile(conv_build, session=design.session, tuned=space, db=db)
    assert d3.tuned_candidate is not None
    assert d3.config == space.to_config(d3.tuned_candidate)
    # and a miss on an empty DB is loud, keeping the given config
    from repro.tune import TuningDB
    empty = TuningDB(tmp_path / "empty.json")
    with caplog.at_level(logging.WARNING, logger="repro"):
        d4 = hls.compile(conv_build, session=design.session, tuned=space,
                         db=empty)
    assert d4.tuned_candidate is None
    assert str(empty.path) in caplog.text
