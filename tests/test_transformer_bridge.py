"""The sequence-model bridge vocabulary (Attention / MLP / RMSNorm).

Acceptance for the million-op compile path: the transformer encoder block
lowers through ``hls.compile`` exactly like the hand-written
``frontend.transformer_encoder_block`` (same ``graph_fingerprint``), the
compiled design matches the tensor twin (fp32 tight — the twin mirrors the
DFG's Taylor-exp softmax — and quantised loose), and the registry fast
paths resolve for the new node patterns.

A reduced geometry (seq=4, d_model=8) keeps CI fast; the full
whisper_tiny-shaped block is exercised by the transformer-smoke CI job and
``benchmarks/bench_compile_scaling.py``.
"""

import functools

import jax
import numpy as np
import pytest

import repro.hls as hls
from repro.core import emit, frontend
from repro.core.pipeline import graph_fingerprint
from repro.core.precision import FORMATS
from repro.kernels import registry as kreg
from repro.models import transformer
from repro.nn import graph as nng
from repro.nn.module import init_tree

SEQ, D, H, F = 4, 8, 2, 16


@pytest.fixture(scope="module")
def session():
    return hls.Session()


@pytest.fixture(scope="module")
def params():
    return init_tree(transformer.specs(SEQ, D, H, F), jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def design(session, params):
    model = transformer.build(SEQ, D, H, F, params=params)
    return session.compile(model, name="toy_encoder_block")


def _hand_build(ctx):
    frontend.transformer_encoder_block(ctx, seq=SEQ, d_model=D, n_heads=H,
                                       ffn=F)


def test_fingerprint_equals_handwritten(design):
    g_hand = hls.trace(_hand_build)
    assert design.fingerprint == graph_fingerprint(g_hand)


def test_design_hash_equals_handwritten(design, session):
    hits = session.stats()["hits"]
    d_hand = session.compile(_hand_build, name="toy_encoder_hand")
    assert d_hand.design_hash == design.design_hash
    assert session.stats()["hits"] == hits + 1


def test_vocabulary_registered():
    assert {nng.RMSNorm, nng.Attention, nng.MLP} <= set(nng.NODE_TYPES)
    model = transformer.build(SEQ, D, H, F)
    specs = model.specs()
    assert set(specs) == {"attn", "mlp", "ln_post"}
    assert specs["attn"]["q"]["kernel"].shape == (D, H, D // H)
    assert specs["attn"]["o"]["kernel"].shape == (H, D // H, D)
    assert specs["mlp"]["fc1"]["w"].shape == (F, D)
    assert specs["ln_post"]["gamma"].shape == (D,)


def test_run_matches_tensor_twin_fp32(design, params):
    """The twin mirrors the DFG's functional model (Taylor-exp softmax,
    sum*(1/D) rms), so fp32 agreement is to rounding, not approximation."""
    x = np.random.default_rng(1).normal(0, 0.5, (2, SEQ, D)) \
        .astype(np.float32)
    got = np.asarray(design.run(x)["ln_post_out"])
    want = np.asarray(transformer.forward(params, x, n_heads=H))
    assert got.shape == (2, SEQ, D)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_quantised_evaluate_matches_tensor_twin(design, params):
    """(wE,wF)-quantised DFG vs the fmt-quantised twin: the twin quantises
    per layer, the DFG per op — BraggNN-style loose tolerances."""
    model = transformer.build(SEQ, D, H, F)
    x = np.random.default_rng(2).normal(0, 0.5, (1, SEQ, D)) \
        .astype(np.float32)
    feeds = {**model.weight_feeds(params), "input": x}
    got = np.asarray(emit.evaluate(design.compiled.graph_opt, feeds,
                                   fmt=FORMATS["5_11"])["ln_post_out"])
    want = np.asarray(transformer.forward(params, x, n_heads=H, fmt="5_11"))
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-3)


def test_pallas_nest_tier_taylor_and_flash(design, params):
    model = transformer.build(SEQ, D, H, F)
    x = np.random.default_rng(3).normal(0, 0.5, (2, SEQ, D)) \
        .astype(np.float32)
    feeds = {**model.weight_feeds(params), "input": x}
    want = np.asarray(transformer.forward(params, x, n_heads=H))

    fn = design.jax_fn()
    assert fn.plan.mode == "nests"
    assert fn.plan.kernels.get("smallfloat_matmul", 0) >= 2
    assert fn.plan.kernels.get("fused_softmax", 0) == 1
    got = np.asarray(fn(feeds)["ln_post_out"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # flash throughput mode: true-exp softmax, so only approximately equal
    fnf = design.jax_fn(nlb_flash=True)
    assert fnf.plan.kernels.get("flash_attention", 0) == 1
    gotf = np.asarray(fnf(feeds)["ln_post_out"])
    np.testing.assert_allclose(gotf, want, rtol=5e-2, atol=5e-3)


def test_registry_patterns_resolve():
    assert kreg.for_pattern("Attention").name == "flash_attention"
    assert kreg.for_pattern("Attention.soft").name == "fused_softmax"
    assert kreg.for_pattern("Attention.proj").name == "smallfloat_matmul"
    assert kreg.for_pattern("MLP").name == "smallfloat_matmul"


def test_no_residual_no_norm_variants_lower():
    """The sub-block flags change the emitted structure, not just params."""
    nodes = [nng.Attention("attn", d_model=D, n_heads=H, pre_norm=False,
                           residual=False),
             nng.RMSNorm("ln_post", dim=D)]
    model = nng.ModuleGraph("bare_attn", (SEQ, D), nodes)
    g = hls.trace(model)
    full = hls.trace(transformer.build(SEQ, D, H, F))
    assert 0 < len(g.ops) < len(full.ops)
    assert "attn.norm.gamma" not in g.inputs
