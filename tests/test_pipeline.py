"""CompilerDriver / PassManager / design-cache behaviour (the Fig. 1 flow
as one orchestrated entrypoint).

Covers: pass registration + unknown-pass error, fixpoint termination,
PassReport op-count deltas, cache hit/miss semantics (including the on-disk
layer), and bit-for-bit equivalence of ``CompilerDriver.compile()`` with
the historical hand-stitched optimize + list_schedule + emit flow on
BraggNN(s=1).
"""

import numpy as np
import pytest

from repro.core import (CompilerConfig, CompilerDriver, Context, PassManager,
                        emit, frontend, passes, pipeline, verify)
from repro.core.schedule import list_schedule


def _small_build(ctx):
    x = ctx.memref("input", (1, 1, 6, 6), "input")
    w = ctx.memref("w", (2, 1, 3, 3), "weight")
    b = ctx.memref("b", (2,), "weight")
    out = ctx.memref("out", (1, 2, 4, 4), "output")
    frontend.conv2d(ctx, x, w, b, out)


def _trace(build):
    ctx = Context()
    build(ctx)
    return ctx.finalize()


# -- registry ----------------------------------------------------------------


def test_builtin_passes_registered():
    assert set(passes.DEFAULT_PIPELINE) <= set(pipeline.PASS_REGISTRY)


def test_unknown_pass_raises():
    with pytest.raises(ValueError, match="unknown pass"):
        PassManager(("cse", "not_a_pass"))


def test_register_pass_decorator_and_duplicate_rejected():
    @pipeline.register_pass("identity_test_pass")
    def identity(g):
        return g

    try:
        assert "identity_test_pass" in pipeline.PASS_REGISTRY
        g, reports = PassManager(("identity_test_pass",), max_rounds=2).run(
            _trace(_small_build))
        assert reports[0].ops_delta == 0
        with pytest.raises(ValueError, match="already registered"):
            pipeline.register_pass("identity_test_pass")(identity)
    finally:
        del pipeline.PASS_REGISTRY["identity_test_pass"]


# -- PassManager -------------------------------------------------------------


def test_fixpoint_terminates_in_one_extra_round():
    """Once a full round leaves the op count unchanged, the loop stops."""
    g = _trace(_small_build)
    pm = PassManager(max_rounds=10)
    g_opt, reports = pm.run(g)
    rounds = {r.round for r in reports}
    # the pipeline must converge well before the round cap
    assert max(rounds) < 9
    # re-running the converged graph is a no-op round
    g_again, reports2 = PassManager(max_rounds=10).run(g_opt)
    assert len(g_again.ops) == len(g_opt.ops)
    assert {r.round for r in reports2} == {0}


def test_pass_reports_deltas_and_histograms():
    g = _trace(_small_build)
    g_opt, reports = PassManager().run(g)
    assert reports, "at least one pass application"
    for rep in reports:
        assert rep.ops_after - rep.ops_before == rep.ops_delta
        assert sum(rep.hist_before.values()) == rep.ops_before
        assert sum(rep.hist_after.values()) == rep.ops_after
        # hist_delta only reports opcodes whose count changed
        for k, v in rep.hist_delta().items():
            assert v != 0
            assert rep.hist_after.get(k, 0) - rep.hist_before.get(k, 0) == v
    # the pipeline as a whole must shrink this conv (cse/dce fire)
    assert len(g_opt.ops) < len(g.ops)


def test_topo_check_and_spot_verify_hooks():
    g = _trace(_small_build)
    pm = PassManager(topo_check=True, spot_verify=True)
    g_opt, reports = pm.run(g)
    executed = [r for r in reports if not r.skipped]
    assert executed, "at least one executed pass application"
    for rep in executed:
        assert rep.topo_ok is True
        assert rep.spot_err is not None
        # reassociation may change rounding, but only slightly
        assert rep.spot_err < 1e-3
    for rep in reports:
        if rep.skipped:
            # a skipped application is a proven no-op: no wall time, no
            # graph change, hooks not re-run
            assert rep.wall_s == 0.0
            assert rep.ops_delta == 0
            assert rep.hist_before == rep.hist_after


# -- cache -------------------------------------------------------------------


def test_cache_hit_on_identical_content_miss_on_config_change(tmp_path):
    driver = CompilerDriver(cache_dir=tmp_path)
    d1 = driver.compile(_small_build, name="a")
    assert (driver.cache.hits, driver.cache.misses) == (0, 1)
    d2 = driver.compile(_small_build, name="b")
    assert (driver.cache.hits, driver.cache.misses) == (1, 1)
    # served from memory: relabeled for this caller, artifacts shared
    assert d2.name == "b"
    assert d2.graph_opt is d1.graph_opt
    assert d2.schedule is d1.schedule

    # changed pipeline config -> different hash -> miss
    cfg = CompilerConfig(pipeline=("cse", "dce"))
    d3 = driver.compile(_small_build, name="c", config=cfg)
    assert driver.cache.misses == 2
    assert d3.design_hash != d1.design_hash

    # fresh driver sharing the disk cache: hit without recompiling
    driver2 = CompilerDriver(cache_dir=tmp_path)
    d4 = driver2.compile(_small_build, name="d")
    assert (driver2.cache.hits, driver2.cache.misses) == (1, 0)
    assert d4.design_hash == d1.design_hash
    assert d4.makespan == d1.makespan
    # nothing compiled is pickled: the loaded design lowers on demand
    feeds = verify.random_feeds(d4.graph_raw, batch=2, seed=3)
    np.testing.assert_allclose(
        np.asarray(d4.jax_fn()(feeds)["out"]),
        np.asarray(d1.jax_fn()(feeds)["out"]), rtol=1e-5, atol=1e-6)


def test_graph_fingerprint_stable_across_retrace():
    g1, g2 = _trace(_small_build), _trace(_small_build)
    assert pipeline.graph_fingerprint(g1) == pipeline.graph_fingerprint(g2)


def test_cache_distinguishes_different_programs():
    def other_build(ctx):
        x = ctx.memref("input", (1, 1, 6, 6), "input")
        out = ctx.memref("out", (1, 1, 2, 2), "output")
        frontend.max_pool_2d(ctx, x, out, k=3, stride=2)

    driver = CompilerDriver()
    d1 = driver.compile(_small_build)
    d2 = driver.compile(other_build)
    assert d1.design_hash != d2.design_hash
    assert driver.cache.misses == 2


# -- equivalence with the hand-stitched flow ---------------------------------


def test_compile_equals_hand_stitched_flow_on_braggnn():
    """Driver output matches optimize + list_schedule + emit bit-for-bit."""
    build = lambda ctx: frontend.braggnn(ctx, s=1)

    # hand-stitched (the historical consumer-side recipe)
    ctx = Context(forward=True)
    build(ctx)
    g_raw = ctx.finalize()
    g_opt = passes.optimize(g_raw)
    sched = list_schedule(g_opt)

    driver = CompilerDriver()
    design = driver.compile(build, name="braggnn_s1")

    assert len(design.graph_raw.ops) == len(g_raw.ops)
    assert len(design.graph_opt.ops) == len(g_opt.ops)
    assert [(o.opcode, o.args, o.result) for o in design.graph_opt.ops] == \
           [(o.opcode, o.args, o.result) for o in g_opt.ops]
    assert design.makespan == sched.makespan
    assert design.schedule.start == sched.start
    assert design.schedule.resource_units == sched.resource_units

    # identical numerics: functional sim through both flows
    feeds = verify.random_feeds(g_raw, batch=4, seed=0, scale=0.4)
    out_hand = emit.evaluate(g_opt, feeds)
    out_drv = design.evaluate(feeds)
    for k in out_hand:
        np.testing.assert_array_equal(out_hand[k], out_drv[k])
    err_hand = max(float(np.max(np.abs(
        emit.evaluate(g_raw, feeds)[k] - out_hand[k]))) for k in out_hand)
    err_drv = max(float(np.max(np.abs(
        design.evaluate(feeds, raw=True)[k] - out_drv[k])))
        for k in out_drv)
    assert err_hand == err_drv

    # second compile of the same config is served from cache
    before_hits = driver.cache.hits
    again = driver.compile(build, name="braggnn_s1")
    assert driver.cache.hits == before_hits + 1
    assert again is design


def test_run_testbench_accepts_compiled_design():
    driver = CompilerDriver()
    design = driver.compile(_small_build, name="conv_tb")
    rep = verify.run_testbench("conv_tb", design=design)
    assert rep.passed
    assert rep.makespan == design.makespan
    # and the build-callable path still works and agrees
    rep2 = verify.run_testbench("conv_tb", _small_build)
    assert rep2.passed
    assert rep2.makespan == rep.makespan


def test_session_stats_accounting():
    """hls.Session.stats() surfaces the DesignCache hit/miss counters and
    the driver's recompile count with exact bookkeeping."""
    import repro.hls as hls
    s = hls.Session()
    st0 = s.stats()
    assert st0 == {"hits": 0, "misses": 0, "recompiles": 0,
                   "memory_entries": 0, "pass_memo_entries": 0,
                   "pass_memo_hits": 0}

    s.compile(_small_build, name="acct")          # cold: one miss
    st1 = s.stats()
    assert st1["misses"] == 1 and st1["hits"] == 0
    assert st1["recompiles"] == 1
    assert st1["memory_entries"] == 1

    s.compile(_small_build, name="acct")          # warm: one hit, no compile
    st2 = s.stats()
    assert st2["hits"] == 1 and st2["misses"] == 1
    assert st2["recompiles"] == 1                 # unchanged
    assert st2["memory_entries"] == 1

    # a config change is a genuine recompile, not a cache hit
    s.compile(_small_build, name="acct",
              config=CompilerConfig(pipeline=("cse", "dce")))
    st3 = s.stats()
    assert st3["misses"] == 2 and st3["recompiles"] == 2
    assert st3["memory_entries"] == 2


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_enable_compile_cache_path(from_env, tmp_path, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    without it the cache sits at one fixed path inside the checkout."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core import cachedir
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = cachedir.enable_compile_cache()
        if from_env:
            assert path == tmp_path
            assert jax.config.jax_compilation_cache_dir == prev_dir
        else:
            assert path == cachedir.REPO_ROOT / ".jax_cache"
            assert (cachedir.REPO_ROOT / "src" / "repro").is_dir()
            assert jax.config.jax_compilation_cache_dir == str(path)
            assert cachedir.enable_compile_cache() == path   # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        compilation_cache.reset_cache()
