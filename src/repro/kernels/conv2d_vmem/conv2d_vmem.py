"""Pallas TPU kernel: weights-resident convolution as one MXU contraction
(BraggNN path).

The paper's headline resource result is that at (5,4)/(5,3) precision the
*entire* BraggNN weight set fits in registers/LUTs — no BRAM.  The TPU
analogue: the conv's weights live in VMEM for the kernel's lifetime (~59 KB
for all of BraggNN at s=1) while the batch streams through in row blocks.
Valid padding, stride 1, NCHW — matching the loop-nest semantics of
``repro.core.frontend.conv2d``.

The wrapper lowers the conv to im2col as XLA ops: the ``(B·Ho·Wo,
Cin·kh·kw)`` patch matrix and the ``(Cin·kh·kw, Cout)`` weight matrix.  The
kernel is then a single 2-D contraction per row block with bias, ReLU and
(wE,wF) operand quantisation (in VMEM, the FloPoCo discipline) fused.  A
per-tap contraction over NCHW blocks needs in-kernel reshapes across the
lane axis (e.g. 81 lanes at img=11), which the TPU compiler refuses.

Grid: (rows / bm,).  Per step: patch block (bm, K) + full weights (K, Cout)
+ bias (1, Cout) -> out block (bm, Cout).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.smallfloat_matmul.smallfloat_matmul import (
    _dot, _quantize_block)


def _conv_kernel(p_ref, w_ref, b_ref, o_ref, *, fmt, fuse_relu):
    p = p_ref[...].astype(jnp.float32)            # (bm, Cin·kh·kw)
    w = w_ref[...].astype(jnp.float32)            # (Cin·kh·kw, Cout)
    if fmt is not None:
        p = _quantize_block(p, *fmt)
        w = _quantize_block(w, *fmt)
    acc = _dot(p, w)
    if b_ref is not None:
        acc = acc + b_ref[...].astype(jnp.float32)
    if fuse_relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc


def _conv_kernel_nobias(p_ref, w_ref, o_ref, **kw):
    _conv_kernel(p_ref, w_ref, None, o_ref, **kw)


def im2col(x: jax.Array, kh: int, kw: int) -> jax.Array:
    """(B, Cin, H, W) -> (B·Ho·Wo, Cin·kh·kw) valid-conv patch matrix.

    Column order is ``(cin, i, j)``, matching ``w.reshape(Cout, -1)``.
    """
    b, cin, h, wdim = x.shape
    ho, wo = h - kh + 1, wdim - kw + 1
    taps = [x[:, :, i:i + ho, j:j + wo] for i in range(kh) for j in range(kw)]
    p = jnp.stack(taps, axis=2)                   # (B, Cin, kh·kw, Ho, Wo)
    return p.transpose(0, 3, 4, 1, 2).reshape(b * ho * wo, cin * kh * kw)


@functools.partial(jax.jit, static_argnames=(
    "fmt", "fuse_relu", "bm", "interpret"))
def conv2d_vmem(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                *, fmt: Optional[tuple[int, int]] = None,
                fuse_relu: bool = False, bm: int = 512,
                interpret: bool = False) -> jax.Array:
    """x: (B, Cin, H, W), w: (Cout, Cin, kh, kw), b: (Cout,) -> fp32.

    ``bm`` is the patch rows per grid step; rows are zero-padded up to a
    whole number of blocks when there is more than one block.
    """
    bsz, cin, h, wdim = x.shape
    cout, cin2, kh, kw = w.shape
    if cin != cin2:
        raise ValueError(f"input channels differ: x {x.shape}, w {w.shape}")
    ho, wo = h - kh + 1, wdim - kw + 1
    patches = im2col(x, kh, kw)
    rows, kdim = patches.shape
    bm = min(bm, rows)
    rows_p = -(-rows // bm) * bm
    if rows_p != rows:
        patches = jnp.pad(patches, ((0, rows_p - rows), (0, 0)))
    wmat = w.reshape(cout, kdim).T

    in_specs = [
        pl.BlockSpec((bm, kdim), lambda i: (i, 0)),
        pl.BlockSpec((kdim, cout), lambda i: (0, 0)),
    ]
    args = [patches, wmat]
    kernel = _conv_kernel_nobias
    if b is not None:
        # bias kept 2-D: TPU VMEM tiles are (sublane, lane)-shaped
        in_specs.append(pl.BlockSpec((1, cout), lambda i: (0, 0)))
        args.append(b.reshape(1, cout))
        kernel = _conv_kernel
    out = pl.pallas_call(
        functools.partial(kernel, fmt=fmt, fuse_relu=fuse_relu),
        grid=(rows_p // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, cout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, cout), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:rows].reshape(bsz, ho, wo, cout).transpose(0, 3, 1, 2)
