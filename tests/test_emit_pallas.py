"""The compiled rendering (``emit_pallas``) vs the functional simulator.

Equivalence (``to_pallas_fn`` vs ``emit.evaluate``, fp32 and quantised) on
a conv2d design and on bridged BraggNN(s=1); the per-group tensor fallback
path; the kernel registry's pattern table; the one serving path that
``serve``, ``engine`` and ``trigger`` take by default; and the refusal of
any other ``backend``.
"""

import numpy as np
import pytest

import repro.hls as hls
from repro.core import emit, frontend, verify
from repro.core.emit_pallas import to_pallas_fn
from repro.core.precision import FORMATS
from repro.kernels import registry
from repro.models import braggnn

jax = pytest.importorskip("jax")


def conv_build(ctx):
    x = ctx.memref("input", (1, 3, 8, 8), "input")
    w = ctx.memref("weight", (4, 3, 3, 3), "weight")
    b = ctx.memref("bias", (4,), "weight")
    out = ctx.memref("out", (1, 4, 6, 6), "output")
    frontend.conv2d(ctx, x, w, b, out)


@pytest.fixture(scope="module")
def conv_design():
    return hls.Session().compile(conv_build, name="conv_pallas")


@pytest.fixture(scope="module")
def conv_feeds(conv_design):
    return verify.random_feeds(conv_design.graph_raw, batch=3, seed=0)


@pytest.fixture(scope="module")
def bragg_design():
    m = braggnn.build(1, img=9)
    module = m.bind(m.init_params(jax.random.PRNGKey(0)))
    return hls.compile(module)


@pytest.fixture(scope="module")
def bragg_feeds(bragg_design):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 1, 1, 9, 9)).astype(np.float32) * 0.2
    return bragg_design.feeds({"input": x})


# ---------------------------------------------------------------------------
# Generic DFG tier: conv2d design
# ---------------------------------------------------------------------------


def test_conv_dfg_matches_evaluate_fp32(conv_design, conv_feeds):
    g = conv_design.graph_opt
    ref = emit.evaluate(g, conv_feeds)
    fn = to_pallas_fn(g)
    out = fn(conv_feeds)
    assert fn.plan.mode == "dfg"
    assert fn.plan.n_segments >= 1
    assert not fn.plan.fallbacks
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-5, atol=1e-4)


def test_conv_dfg_matches_evaluate_quantised(conv_design, conv_feeds):
    """With ``fmt`` the dfg tier re-quantises per op — the FloPoCo
    functional model, matching ``emit.evaluate`` tightly."""
    g = conv_design.graph_opt
    ref = emit.evaluate(g, conv_feeds, fmt=FORMATS["5_4"])
    fn = to_pallas_fn(g, fmt="5_4")
    out = fn(conv_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k], atol=1e-5)


def test_conv_dfg_real_pallas_call_interpret(conv_design, conv_feeds):
    """Force real ``pl.pallas_call`` segment bodies (interpret mode on
    CPU) — the CI pallas-smoke path."""
    g = conv_design.graph_opt
    ref = emit.evaluate(g, conv_feeds)
    fn = to_pallas_fn(g, use_pallas=True)
    assert fn.plan.use_pallas and fn.plan.interpret
    assert "interpret=True" in fn.plan.summary()
    out = fn(conv_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-5, atol=1e-4)


def test_conv_dfg_per_group_fallback(conv_design, conv_feeds):
    """Groups whose opcode is missing from the table run on the tensor
    path and are recorded in the plan — results unchanged."""
    g = conv_design.graph_opt
    table = {k: v for k, v in registry.OPCODE_KERNELS.items()
             if k != "fmac"}
    ref = emit.evaluate(g, conv_feeds)
    fn = to_pallas_fn(g, opcode_table=table)
    out = fn(conv_feeds)
    assert fn.plan.fallbacks, "dropping fmac must force fallbacks"
    assert all("fmac" in f for f in fn.plan.fallbacks)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-5, atol=1e-4)


def test_dfg_unbatched_feeds_broadcast(conv_design):
    feeds = verify.random_feeds(conv_design.graph_raw, batch=1, seed=3)
    unbatched = {k: np.asarray(v)[0] for k, v in feeds.items()}
    ref = emit.evaluate(conv_design.graph_opt, unbatched)
    out = to_pallas_fn(conv_design.graph_opt)(unbatched)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# BraggNN: nest-pattern tier + quantised dfg tier
# ---------------------------------------------------------------------------


def test_braggnn_nest_tier_matches_evaluate(bragg_design, bragg_feeds):
    g = bragg_design.graph_opt
    ref = emit.evaluate(g, bragg_feeds)
    fn = bragg_design.jax_fn()
    assert fn.plan.mode == "nests"
    assert fn.plan.kernels, "registry kernels must serve the bridged nests"
    assert any(k.startswith("conv2d_vmem") for k in fn.plan.kernels)
    assert any(k.startswith("smallfloat_matmul") for k in fn.plan.kernels)
    assert any(k.startswith("fused_softmax") for k in fn.plan.kernels)
    out = fn(bragg_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-4, atol=1e-5)


def test_braggnn_nest_tier_pallas_interpret_matches_evaluate(bragg_design,
                                                           bragg_feeds):
    """The chip path's kernels (conv2d_vmem, smallfloat_matmul,
    fused_softmax) through ``pl.pallas_call``, here in the interpreter."""
    g = bragg_design.graph_opt
    ref = emit.evaluate(g, bragg_feeds)
    fn = bragg_design.jax_fn(use_pallas=True)
    assert fn.plan.use_pallas and fn.plan.interpret
    assert not fn.plan.fallbacks
    assert "Pallas interpreter" in fn.plan.summary()
    out = fn(bragg_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("s,blocks", [
    # s=1 keeps the blocking it always had: each dimension one block
    (1, [(50, 16, "tiled"), (16, 8, "tiled"), (8, 4, "tiled"),
         (4, 2, "tiled")]),
    # s=4: K = 200 divides by no 128-block and is taken whole
    (4, [(200, 64, "wholek"), (64, 32, "tiled"), (32, 16, "tiled"),
         (16, 2, "tiled")]),
])
def test_nest_tier_records_matmul_blocking(s, blocks):
    from repro import obs
    obs.enable()
    obs.reset()
    try:
        fn = to_pallas_fn(None, module=braggnn.build(s, 11), mode="nests")
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    got = [(b.bk, b.bn, b.tag) for b in fn.plan.blocks.values()]
    assert list(fn.plan.blocks) == [f"dense{i}" for i in range(4)]
    assert got == blocks
    for tag in {t for _, _, t in blocks}:
        assert counters[f"pallas.kernel.smallfloat_matmul:{tag}"] == \
            sum(t == tag for _, _, t in blocks)


@pytest.mark.parametrize("s", [1, 4])
def test_nest_tier_records_conv_forms(s):
    """BraggNN's three 3×3 convs take the unrolled form and the non-local
    block's four 1×1 convs the pointwise form, at s=1 and at s=4; none
    takes im2col."""
    from repro import obs
    obs.enable()
    obs.reset()
    try:
        fn = to_pallas_fn(None, module=braggnn.build(s, 11), mode="nests")
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    assert fn.plan.convs == {
        "conv1": "unrolled", "nlb.theta": "pointwise", "nlb.phi": "pointwise",
        "nlb.g": "pointwise", "nlb.out_cnn": "pointwise", "conv2a": "unrolled",
        "conv2b": "unrolled"}
    assert counters["pallas.kernel.conv2d_vmem:unrolled"] == 3
    assert counters["pallas.kernel.conv2d_vmem:pointwise"] == 4
    assert "pallas.kernel.conv2d_vmem:im2col" not in counters


def test_dfg_tier_on_tpu_runs_xla_bodies(conv_design, conv_feeds,
                                         monkeypatch):
    """On the TPU the DFG tier runs XLA segment bodies by rule, and
    ``use_pallas=True`` is refused with the missing Mosaic gather named;
    the nest tier defaults to Mosaic kernels there."""
    from repro.core import emit_pallas
    monkeypatch.setattr(emit_pallas, "_on_tpu", lambda: True)
    g = conv_design.graph_opt
    fn = to_pallas_fn(g)
    assert not fn.plan.use_pallas and not fn.plan.interpret
    assert "XLA segment bodies" in fn.plan.summary()
    ref = emit.evaluate(g, conv_feeds)
    out = fn(conv_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k],
                                   rtol=1e-5, atol=1e-4)
    with pytest.raises(NotImplementedError, match="Mosaic.*gather"):
        to_pallas_fn(g, use_pallas=True)


def test_nest_tier_on_tpu_plans_mosaic_kernels(bragg_design, monkeypatch):
    from repro.core import emit_pallas
    monkeypatch.setattr(emit_pallas, "_on_tpu", lambda: True)
    for fmt in (None, "5_4"):
        plan = bragg_design.jax_fn(fmt=fmt).plan
        assert plan.use_pallas and not plan.interpret
        assert not plan.fallbacks
        assert "use_pallas=True interpret=False" in plan.summary()
        assert plan.summary().endswith("Mosaic kernels")


def test_braggnn_dfg_tier_quantised_matches_evaluate(bragg_design,
                                                     bragg_feeds):
    g = bragg_design.graph_opt
    ref = emit.evaluate(g, bragg_feeds, fmt=FORMATS["5_4"])
    fn = bragg_design.jax_fn(mode="dfg", fmt="5_4")
    assert fn.plan.mode == "dfg"
    out = fn(bragg_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k], atol=1e-5)


def test_braggnn_scatter_gather_fusion_happens(bragg_design):
    fn = bragg_design.jax_fn(mode="dfg")
    assert fn.plan.fused_scatters > 0, \
        "aligned scatter->gather pairs must be forwarded in-register"


def test_nest_tier_rejects_per_sample_weights(bragg_design):
    feeds = verify.random_feeds(bragg_design.graph_raw, batch=2, seed=1)
    fn = bragg_design.jax_fn()
    with pytest.raises(ValueError, match="varies across the batch"):
        fn(feeds)


def test_nest_tier_flash_attention_mode(bragg_design, bragg_feeds):
    """The flash-attention NLB throughput mode: a true-exp softmax, so an
    approximation of the Taylor functional model — recorded as a note."""
    g = bragg_design.graph_opt
    ref = emit.evaluate(g, bragg_feeds)
    fn = bragg_design.jax_fn(nlb_flash=True)
    assert "flash_attention" in fn.plan.kernels
    assert any("flash" in n for n in fn.plan.notes)
    out = fn(bragg_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), ref[k], atol=5e-2)


def _nlb_true_softmax(p, x):
    """The non-local block in float32 at HIGHEST with a true-exp softmax,
    NCHW throughout: x + out(softmax(θᵀφ) g)."""
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    b, c1, h, w = x.shape

    def conv(wt, a):
        return jnp.einsum("oc,bcp->bop", wt[:, :, 0, 0], a, precision=hi)

    xf = x.reshape(b, c1, h * w)
    t, ph, g = (conv(p[k]["w"], xf) for k in ("theta", "phi", "g"))
    a = jax.nn.softmax(jnp.einsum("bci,bcj->bij", t, ph, precision=hi), -1)
    y = jnp.einsum("bij,bcj->bci", a, g, precision=hi)
    return (xf + conv(p["out"]["w"], y)).reshape(x.shape)


@pytest.mark.parametrize("use_pallas", [None, True],
                         ids=["oracle", "interpret"])
def test_nest_tier_flash_nlb_matches_true_softmax(use_pallas):
    """The non-local block alone with ``nlb_flash=True``: the channel-major
    layout through flash attention equals a true-exp softmax block, at a
    batch whose B·81 pixel columns fill no whole block.  θ and φ are
    scaled so the scores spread over several units and the softmax is far
    from uniform."""
    from repro.nn import graph as nng
    nlb = nng.NonLocalBlock("nlb", channels=16, mid_channels=8)
    module = nng.ModuleGraph("nlb_only", (1, 16, 9, 9), [nlb])
    params = module.init_params(jax.random.PRNGKey(3))
    for k in ("theta", "phi"):
        params["nlb"][k]["w"] = params["nlb"][k]["w"] * 3.0
    fn = to_pallas_fn(None, module=module.bind(params), mode="nests",
                      nlb_flash=True, use_pallas=use_pallas)
    assert "flash_attention" in fn.plan.kernels
    x = np.random.default_rng(1).standard_normal(
        (5, 1, 16, 9, 9)).astype(np.float32)
    (got,) = fn({module.input_name: x}).values()
    want = np.asarray(_nlb_true_softmax(params["nlb"], x[:, 0]))
    got = np.asarray(got).reshape(want.shape)
    assert np.abs(want - x[:, 0]).max() > 0.1     # the block does work
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Serving + validation contract
# ---------------------------------------------------------------------------


def test_serve_pallas_backend_and_report(conv_design, conv_feeds):
    rep = conv_design.serve([conv_feeds, conv_feeds], backend="pallas",
                            collect=True)
    assert rep.served and rep.served.startswith("pallas[dfg]")
    assert rep.batches == 2 and rep.samples == 6
    ref = emit.evaluate(conv_design.graph_opt, conv_feeds)
    for k in ref:
        np.testing.assert_allclose(np.asarray(rep.outputs[0][k]), ref[k],
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("tier", ["nests", "dfg"])
def test_default_serving_path_is_compiled(tier, request, conv_feeds):
    """With no ``backend``, a bridged design serves, batches and triggers
    through the nest tier; a loop nest serves through the DFG tier (its
    weights are graph inputs, so only ``serve`` takes them)."""
    if tier == "dfg":
        design = request.getfixturevalue("conv_design")
        rep = design.serve([conv_feeds], collect=True)
        assert rep.served.startswith("pallas[dfg]")
        ref = emit.evaluate(design.graph_opt, conv_feeds)
        for k in ref:
            np.testing.assert_allclose(np.asarray(rep.outputs[0][k]), ref[k],
                                       rtol=1e-5, atol=1e-4)
        return
    from repro import trigger
    design = request.getfixturevalue("bragg_design")
    x = np.zeros((2, 1, 1, 9, 9), np.float32)
    assert design.serve([x]).served.startswith("pallas[nests]")
    eng = design.engine(buckets=(2,))
    assert eng.report().served.startswith("pallas[nests]")
    loop = design.trigger(window=2, threshold=0.0)
    assert loop.served.startswith("pallas[nests]")
    rep = loop.run(trigger.DetectorFeed(img=9, seed=0), 2)
    assert rep.served == loop.served and rep.processed == 2


def test_serve_rejects_unknown_backend(conv_design, conv_feeds):
    """``pallas`` is the only serving path: every other name is refused
    by ``serve``, ``engine`` and ``trigger`` before anything is lowered."""
    for backend in ("tensor", "simd", "veryl"):
        with pytest.raises(ValueError, match="'pallas' is the only"):
            conv_design.serve([conv_feeds], backend=backend)
        with pytest.raises(ValueError, match="'pallas' is the only"):
            conv_design.engine(backend=backend)
        with pytest.raises(ValueError, match="'pallas' is the only"):
            conv_design.trigger(backend=backend)


def test_to_jax_fn_rejects_unknown_backend(conv_design):
    """``jax_fn`` takes lowering keywords only; the SIMD emitter is gone."""
    assert not hasattr(emit, "to_jax_fn")
    for backend in ("simd", "pallas"):
        with pytest.raises(TypeError, match="backend"):
            conv_design.jax_fn(backend=backend)
        with pytest.raises(TypeError, match="backend"):
            conv_design.compiled.jax_fn(backend=backend)


def test_to_pallas_fn_rejects_unknown_mode(conv_design):
    with pytest.raises(ValueError, match="nests, dfg"):
        to_pallas_fn(conv_design.graph_opt, mode="turbo")
    with pytest.raises(ValueError, match="ModuleGraph"):
        to_pallas_fn(conv_design.graph_opt, mode="nests")


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------


def test_registry_has_all_four_exemplars():
    assert registry.names() == ["conv2d_vmem", "flash_attention",
                                "fused_softmax", "smallfloat_matmul"]
    for name in registry.names():
        e = registry.get(name)
        assert callable(e.fn) and callable(e.kernel) and callable(e.oracle)
        assert e.accelerates


@pytest.mark.parametrize("pattern,name", [
    ("Conv2d", "conv2d_vmem"),
    ("Linear", "smallfloat_matmul"),
    ("Softmax", "fused_softmax"),
    ("nlb.soft", "fused_softmax"),
    ("NonLocalBlock.attention", "flash_attention"),
])
def test_registry_pattern_table(pattern, name):
    assert registry.for_pattern(pattern).name == name


def test_registry_rejects_duplicates_and_unknown():
    with pytest.raises(ValueError, match="already registered"):
        registry.register(registry.get("conv2d_vmem"))
    with pytest.raises(KeyError, match="no kernel"):
        registry.get("nope")
    assert registry.for_pattern("Transformer") is None


def test_registry_conv2d_entry_roundtrip():
    key = jax.random.key(0)
    x = jax.random.normal(jax.random.fold_in(key, 0), (2, 3, 9, 9))
    w = jax.random.normal(jax.random.fold_in(key, 1), (4, 3, 3, 3))
    e = registry.get("conv2d_vmem")
    got = e.fn(x, w, None, use_pallas=True, interpret=True)
    want = e.oracle(x, w, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_registry_matmul_entry_fp32_identity_mode():
    """``exp_bits=None`` (the nest tier's fp32 path) must be a plain
    matmul with no quantisation, through both wrapper routes."""
    key = jax.random.key(1)
    x = jax.random.normal(jax.random.fold_in(key, 0), (8, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    e = registry.get("smallfloat_matmul")
    want = np.asarray(x) @ np.asarray(w)
    got_o = e.fn(x, w, exp_bits=None, man_bits=None)
    got_p = e.fn(x, w, exp_bits=None, man_bits=None, use_pallas=True,
                 interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), want, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_p), want, rtol=1e-4,
                               atol=1e-4)


def test_registry_softmax_entry_taylor_mode():
    key = jax.random.key(2)
    x = jax.random.normal(key, (16, 16)) * 0.3
    e = registry.get("fused_softmax")
    got = e.fn(x, taylor_order=8, use_pallas=True, interpret=True)
    want = e.fn(x, taylor_order=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, atol=1e-4)


def test_registry_flash_attention_entry_roundtrip():
    key = jax.random.key(3)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, 16, 1, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 16, 1, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 16, 1, 8))
    e = registry.get("flash_attention")
    got = e.fn(q, k, v, causal=False, use_pallas=True, interpret=True)
    want = e.fn(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
