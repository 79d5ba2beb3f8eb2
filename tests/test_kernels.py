"""Pallas kernels vs their pure-jnp oracles: shape/dtype sweeps in
interpret mode (the TPU-target kernels executed on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision import FloatFormat, quantize
from repro.kernels.conv2d_vmem import conv2d_vmem as conv_mod
from repro.kernels.conv2d_vmem.conv2d_vmem import (
    VMEM_BLOCK_BYTES, conv2d_vmem, conv_form, pointwise_blocks,
    unroll_weights)
from repro.kernels.conv2d_vmem.ref import conv2d_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.fused_softmax.fused_softmax import fused_softmax
from repro.kernels.fused_softmax.ref import fused_softmax_ref
from repro.kernels.smallfloat_matmul.ref import smallfloat_matmul_ref
from repro.kernels.smallfloat_matmul.smallfloat_matmul import (
    blocking, smallfloat_matmul)


def _r(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (64, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("em", [(5, 4), (5, 3), (5, 11)])
def test_smallfloat_matmul_sweep(m, k, n, dtype, em):
    key = jax.random.key(m * n + em[1])
    x = _r(jax.random.fold_in(key, 0), (m, k), dtype)
    w = _r(jax.random.fold_in(key, 1), (k, n), dtype)
    got = smallfloat_matmul(x, w, exp_bits=em[0], man_bits=em[1],
                            interpret=True)
    want = smallfloat_matmul_ref(x, w, exp_bits=em[0], man_bits=em[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_smallfloat_matmul_bias_relu():
    key = jax.random.key(0)
    x = _r(jax.random.fold_in(key, 0), (128, 128), jnp.float32)
    w = _r(jax.random.fold_in(key, 1), (128, 128), jnp.float32)
    b = _r(jax.random.fold_in(key, 2), (128,), jnp.float32)
    got = smallfloat_matmul(x, w, b, fuse_relu=True, interpret=True)
    want = smallfloat_matmul_ref(x, w, b, fuse_relu=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert float(jnp.min(got)) >= 0.0


@pytest.mark.parametrize("b,cin,cout,img,kk", [
    (8, 1, 16, 11, 3), (4, 3, 8, 9, 3), (2, 16, 8, 9, 1), (2, 1, 4, 64, 3)])
@pytest.mark.parametrize("fmt", [None, (5, 4)])
def test_conv2d_vmem_sweep(b, cin, cout, img, kk, fmt):
    key = jax.random.key(b * img)
    x = _r(jax.random.fold_in(key, 0), (b, cin, img, img), jnp.float32)
    w = _r(jax.random.fold_in(key, 1), (cout, cin, kk, kk), jnp.float32)
    bias = _r(jax.random.fold_in(key, 2), (cout,), jnp.float32)
    got = conv2d_vmem(x, w, bias, fmt=fmt, interpret=True)
    want = conv2d_ref(x, w, bias, fmt=fmt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("s,h,kv,d", [(128, 4, 2, 32), (256, 2, 2, 64),
                                      (64, 8, 1, 16)])
@pytest.mark.parametrize("window,cap", [(None, 0.0), (32, 0.0),
                                        (None, 10.0)])
def test_flash_attention_sweep(s, h, kv, d, window, cap):
    key = jax.random.key(s + h)
    q = _r(jax.random.fold_in(key, 0), (2, s, h, d), jnp.float32)
    k = _r(jax.random.fold_in(key, 1), (2, s, kv, d), jnp.float32)
    v = _r(jax.random.fold_in(key, 2), (2, s, kv, d), jnp.float32)
    got = fa_ops.attention(q, k, v, causal=True, window=window,
                           logit_cap=cap, use_pallas=True, interpret=True)
    want = fa_ops.attention(q, k, v, causal=True, window=window,
                            logit_cap=cap, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_matches_model_blockwise():
    """Kernel and the model's XLA blockwise path agree on GQA inputs."""
    from repro.nn import attention as nn_attn
    key = jax.random.key(3)
    B, S, H, K, D = 2, 128, 4, 2, 32
    q = _r(jax.random.fold_in(key, 0), (B, S, H, D), jnp.float32)
    k = _r(jax.random.fold_in(key, 1), (B, S, K, D), jnp.float32)
    v = _r(jax.random.fold_in(key, 2), (B, S, K, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    a = nn_attn.blockwise_attention(q, k, v, q_pos=pos, k_pos=pos,
                                    causal=True, block_size=32)
    b = fa_ops.attention(q, k, v, causal=True, use_pallas=True,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("rows,cols", [(256, 64), (128, 200), (512, 32)])
@pytest.mark.parametrize("taylor", [0, 8])
def test_fused_softmax_sweep(rows, cols, taylor):
    key = jax.random.key(rows + cols)
    x = _r(key, (rows, cols), jnp.float32) * 3.0
    got = fused_softmax(x, taylor_order=taylor, interpret=True)
    want = fused_softmax_ref(x, taylor_order=taylor)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-4)


def test_fused_softmax_taylor_close_to_true_softmax():
    """The paper's Taylor exp (order 8, 2^2 range reduction) approximates
    true softmax to ~1e-3 on the stabilised domain."""
    key = jax.random.key(9)
    x = _r(key, (64, 96), jnp.float32) * 2.0
    approx = fused_softmax(x, taylor_order=8, interpret=True)
    true = fused_softmax_ref(x, taylor_order=0)
    assert float(jnp.max(jnp.abs(approx - true))) < 5e-3


#: BraggNN(s=1, img=11) conv shapes at a small odd batch: (x, w, bias)
BRAGG_CONVS = [((5, 1, 11, 11), (16, 1, 3, 3), True),
               ((5, 16, 9, 9), (8, 16, 1, 1), False),
               ((5, 8, 9, 9), (16, 8, 1, 1), False),
               ((5, 16, 9, 9), (8, 16, 3, 3), True),
               ((5, 8, 7, 7), (2, 8, 3, 3), True)]


@pytest.mark.parametrize("fmt", [None, (5, 4)])
@pytest.mark.parametrize("xs,ws,bias", BRAGG_CONVS,
                         ids=["conv1", "nlb_in", "nlb_out", "conv2a",
                              "conv2b"])
def test_conv2d_vmem_braggnn_shapes(xs, ws, bias, fmt):
    """BraggNN's convs in the form ``conv_form`` gives them (the 3×3 ones
    unrolled, the 1×1 ones pointwise, whose B·H·W pixel columns then fill
    no whole block of 128 and are padded)."""
    key = jax.random.key(xs[1] * 100 + ws[0])
    x = _r(jax.random.fold_in(key, 0), xs, jnp.float32)
    w = _r(jax.random.fold_in(key, 1), ws, jnp.float32) * 0.3
    b = _r(jax.random.fold_in(key, 2), (ws[0],), jnp.float32) \
        if bias else None
    got = conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=bias, bm=64,
                      interpret=True)
    want = conv2d_ref(x, w, b, fmt=fmt, fuse_relu=bias)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("batch", [1, 3, 4, 5])
def test_fused_softmax_unaligned_rows(batch):
    """B·81 rows (the NLB softmax at img=11) with blocks that do not
    divide them: the padded rows must not leak into the result."""
    key = jax.random.key(batch)
    x = _r(key, (batch * 81, 81), jnp.float32) * 2.0
    got = fused_softmax(x, taylor_order=8, block_rows=64, interpret=True)
    want = fused_softmax_ref(x, taylor_order=8)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_smallfloat_matmul_ragged_rows():
    """M not a multiple of the row block: rows are padded and sliced."""
    key = jax.random.key(7)
    x = _r(jax.random.fold_in(key, 0), (200, 50), jnp.float32)
    w = _r(jax.random.fold_in(key, 1), (50, 16), jnp.float32)
    got = smallfloat_matmul(x, w, exp_bits=5, man_bits=4, interpret=True)
    want = smallfloat_matmul_ref(x, w, exp_bits=5, man_bits=4)
    assert got.shape == (200, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k,n,tag", [
    (200, 64, "wholek"),      # BraggNN(s=4)'s dense0
    (150, 48, "wholek"),
    (64, 200, "wholen"),
    (4300, 2, "padk"),        # a whole K block would not fit VMEM
    (8, 4100, "padn"),
])
@pytest.mark.parametrize("fmt", [None, (5, 4)], ids=["fp32", "5_4"])
def test_smallfloat_matmul_untiled_k_and_n(k, n, tag, fmt):
    """K or N that no 128-block divides: taken whole as one block, or
    zero-padded where a whole block would not fit VMEM; rows ragged too."""
    eb, mb = fmt if fmt is not None else (None, None)
    assert blocking(k, n).tag == tag
    key = jax.random.key(k * n)
    x = _r(jax.random.fold_in(key, 0), (300, k), jnp.float32) / np.sqrt(k)
    w = _r(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    b = _r(jax.random.fold_in(key, 2), (n,), jnp.float32)
    got = smallfloat_matmul(x, w, b, exp_bits=eb, man_bits=mb,
                            fuse_relu=True, interpret=True)
    want = smallfloat_matmul_ref(x, w, b, exp_bits=eb, man_bits=mb,
                                 fuse_relu=True)
    assert got.shape == (300, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("fmt", ["5_11", "5_4", "5_3"])
def test_quantize_jit_bit_exact_vs_numpy(fmt):
    """The jitted quantiser (kernels and the DFG tier) and the numpy
    functional model land on the same lattice point for every value:
    powers of two are built from exponent bits, not XLA's inexact exp2."""
    from repro.core.precision import FORMATS, quantize, quantize_np
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(200_000)
         * np.exp(rng.uniform(-12, 12, 200_000))).astype(np.float32)
    x[:4] = [0.0, -0.0, np.inf, np.nan]
    got = np.asarray(jax.jit(lambda v: quantize(v, FORMATS[fmt]))(x))
    np.testing.assert_array_equal(got, quantize_np(x, FORMATS[fmt]))


def test_fmac_opcode_rounds_product_separately():
    """The DFG tier's fmac rounds the product, then the sum, exactly as
    ``emit.evaluate`` does — XLA must not contract it into one FMA."""
    from repro.kernels.registry import OPCODE_KERNELS
    rng = np.random.default_rng(1)
    a = [rng.standard_normal(100_000).astype(np.float32) for _ in range(3)]
    got = np.asarray(jax.jit(lambda *v: OPCODE_KERNELS["fmac"][1](v))(*a))
    np.testing.assert_array_equal(got, a[0] * a[1] + a[2])


#: BraggNN's 3×3 convs, s=1 then s=4: (Cin, H, Cout)
BRAGG_3X3 = [(1, 11, 16), (16, 9, 8), (8, 7, 2),
             (1, 11, 64), (64, 9, 32), (32, 7, 8)]
BRAGG_3X3_IDS = ["conv1", "conv2a", "conv2b",
                 "s4.conv1", "s4.conv2a", "s4.conv2b"]


def _bragg_3x3_inputs(cin, h, cout, batch):
    key = jax.random.key(cin * 100 + cout)
    x = _r(jax.random.fold_in(key, 0), (batch, cin, h, h), jnp.float32)
    w = _r(jax.random.fold_in(key, 1), (cout, cin, 3, 3), jnp.float32) \
        / np.sqrt(cin)
    b = _r(jax.random.fold_in(key, 2), (cout,), jnp.float32)
    return x, w, b


@pytest.mark.parametrize("fmt", [None, (5, 4)])
@pytest.mark.parametrize("cin,h,cout", BRAGG_3X3, ids=BRAGG_3X3_IDS)
def test_conv2d_vmem_unrolled_braggnn(cin, h, cout, fmt):
    """The unrolled form at BraggNN's 3×3 shapes, s=1 and s=4, against the
    oracle, with 11 samples in row blocks of 8 (the last one padded)."""
    assert conv_form(cin, h, h, cout, 3, 3) == "unrolled"
    x, w, b = _bragg_3x3_inputs(cin, h, cout, 11)
    got = conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=True, bm=8,
                      interpret=True)
    want = conv2d_ref(x, w, b, fmt=fmt, fuse_relu=True)
    assert got.shape == want.shape == (11, cout, h - 2, h - 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.fixture
def im2col_only(monkeypatch):
    """Every conv lowered as im2col: no unrolled matrix fits a zero
    budget.  The jitted wrapper's traces are dropped on both sides, since
    the form is fixed when it traces."""
    monkeypatch.setattr(conv_mod, "UNROLL_BYTES", 0)
    conv2d_vmem.clear_cache()
    yield
    conv2d_vmem.clear_cache()


@pytest.mark.parametrize("fmt", [None, (5, 4)])
@pytest.mark.parametrize("cin,h,cout", BRAGG_3X3[:3], ids=BRAGG_3X3_IDS[:3])
def test_conv2d_vmem_im2col_3x3(im2col_only, cin, h, cout, fmt):
    """The im2col form still serves a 3×3 conv (one whose unrolled matrix
    would not fit), at BraggNN(s=1)'s shapes."""
    assert conv_form(cin, h, h, cout, 3, 3) == "im2col"
    x, w, b = _bragg_3x3_inputs(cin, h, cout, 5)
    got = conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=True, bm=64,
                      interpret=True)
    want = conv2d_ref(x, w, b, fmt=fmt, fuse_relu=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cin,h,cout", [(2, 5, 3), (3, 6, 2)])
def test_unroll_weights_holds_w_bit_for_bit(cin, h, cout):
    """Each tap's weight sits where the tap meets its output pixel, bit
    for bit (a -0.0 keeps its sign), and every other entry is +0.0."""
    kh = kw = 3
    w = np.array(_r(jax.random.key(h), (cout, cin, kh, kw), jnp.float32))
    w[0, 0, 1, 2] = -0.0
    t = np.asarray(unroll_weights(jnp.asarray(w), h, h))
    ho = wo = h - 2
    assert t.shape == (cin * h * h, cout * ho * wo)
    want = np.zeros_like(t)
    hit = np.zeros(t.shape, bool)
    for co in range(cout):
        for ci in range(cin):
            for i in range(kh):
                for j in range(kw):
                    for y in range(ho):
                        for x in range(wo):
                            r = (ci * h + y + i) * h + x + j
                            c = (co * ho + y) * wo + x
                            want[r, c] = w[co, ci, i, j]
                            hit[r, c] = True
    np.testing.assert_array_equal(t.view(np.uint32), want.view(np.uint32))
    assert hit.sum() == cout * cin * kh * kw * ho * wo
    assert not t.view(np.uint32)[~hit].any()


def test_quantize_keeps_signed_zeros():
    """(5,4) maps ±0 to ±0, so quantising the weights before they are
    unrolled equals quantising the unrolled matrix."""
    z = jnp.asarray([0.0, -0.0], jnp.float32)
    got = np.asarray(quantize(z, FloatFormat(5, 4)))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  np.asarray(z).view(np.uint32))


@pytest.mark.parametrize("cin,h,cout,k,form", [
    *[(c, h, o, 3, "unrolled") for c, h, o in BRAGG_3X3],
    (16, 9, 8, 1, "pointwise"), (8, 9, 16, 1, "pointwise"),
    (64, 9, 32, 1, "pointwise"), (32, 9, 64, 1, "pointwise"),
    (1, 64, 16, 3, "im2col"),
], ids=[*BRAGG_3X3_IDS, "nlb_in", "nlb_out", "s4.nlb_in", "s4.nlb_out",
        "img64"])
def test_conv_form(cin, h, cout, k, form):
    assert conv_form(cin, h, h, cout, k, k) == form


#: the non-local block's 1×1 convs, s=1 then s=4: (Cin, Cout)
BRAGG_1X1 = [(16, 8), (8, 16), (64, 32), (32, 64)]
BRAGG_1X1_IDS = ["nlb_in", "nlb_out", "s4.nlb_in", "s4.nlb_out"]


@pytest.mark.parametrize("batch", [1, 3, 4, 5])
@pytest.mark.parametrize("bias", [False, True], ids=["plain", "bias_relu"])
@pytest.mark.parametrize("fmt", [None, (5, 4)], ids=["fp32", "5_4"])
@pytest.mark.parametrize("cin,cout", BRAGG_1X1, ids=BRAGG_1X1_IDS)
def test_conv2d_vmem_pointwise(cin, cout, fmt, bias, batch):
    """The pointwise form at the non-local block's shapes against the
    oracle, on the channel-major ``(Cin, B·81)`` view the block passes.
    Blocks of 128 columns: B·81 fills no whole block at these batches, so
    the last block is padded (one whole block at batch 1)."""
    assert conv_form(cin, 9, 9, cout, 1, 1) == "pointwise"
    key = jax.random.key(cin * 1000 + cout * 10 + batch)
    x = _r(jax.random.fold_in(key, 0), (cin, batch * 81), jnp.float32)
    w = _r(jax.random.fold_in(key, 1), (cout, cin, 1, 1), jnp.float32) \
        / np.sqrt(cin)
    b = _r(jax.random.fold_in(key, 2), (cout,), jnp.float32) \
        if bias else None
    got = conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=bias, bm=128,
                      interpret=True)
    want = conv2d_ref(x, w, b, fmt=fmt, fuse_relu=bias)
    assert got.shape == want.shape == (cout, batch * 81)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fmt", [None, (5, 4)], ids=["fp32", "5_4"])
def test_conv2d_vmem_pointwise_nchw(fmt):
    """An NCHW input to a 1×1 conv takes the pointwise form too, through
    the channel-major view and back: it equals the oracle on NCHW, and
    the channel-major call on the same pixels."""
    key = jax.random.key(7)
    x = _r(jax.random.fold_in(key, 0), (3, 16, 9, 9), jnp.float32)
    w = _r(jax.random.fold_in(key, 1), (8, 16, 1, 1), jnp.float32) / 4.0
    b = _r(jax.random.fold_in(key, 2), (8,), jnp.float32)
    got = conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=True, interpret=True)
    want = conv2d_ref(x, w, b, fmt=fmt, fuse_relu=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    xc = x.transpose(1, 0, 2, 3).reshape(16, -1)
    cm = conv2d_vmem(xc, w, b, fmt=fmt, fuse_relu=True, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(cm), np.asarray(got).transpose(1, 0, 2, 3).reshape(8, -1))


def test_conv2d_vmem_pointwise_refuses_a_3x3_channel_major_input():
    with pytest.raises(ValueError, match="1×1"):
        conv2d_vmem(jnp.zeros((16, 81)), jnp.zeros((8, 16, 3, 3)),
                    interpret=True)


@pytest.mark.parametrize("quantised", [False, True], ids=["fp32", "5_4"])
@pytest.mark.parametrize("cin,cout", BRAGG_1X1, ids=BRAGG_1X1_IDS)
def test_pointwise_blocks(cin, cout, quantised):
    """At the offline batch (4096·81 columns) the column block divides the
    columns, so nothing is padded, and a step fits VMEM; at the trigger's
    batch (4·81) the columns are one whole block."""
    n = 4096 * 81
    blk = pointwise_blocks(cin, cout, n, quantised=quantised)
    assert blk.bm == cout and blk.np_ == n and n % blk.bn == 0
    assert blk.bn % 128 == 0 and blk.bn >= 4096
    step = 4 * (2 * (cin + cout) + cout + (3 * cin if quantised else 0))
    assert step * blk.bn <= VMEM_BLOCK_BYTES
    assert pointwise_blocks(cin, cout, 324, quantised=quantised) == \
        (cout, 324, 324)
    padded = pointwise_blocks(cin, cout, 405, quantised=quantised, cap=128)
    assert padded == (cout, 128, 512)
