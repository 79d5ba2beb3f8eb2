"""Pallas TPU kernel: weights-resident convolution as one MXU contraction
(BraggNN path).

The paper's headline resource result is that at (5,4)/(5,3) precision the
*entire* BraggNN weight set fits in registers/LUTs — no BRAM.  The TPU
analogue: the conv's weights live in VMEM for the kernel's lifetime while
the batch streams through in row blocks.  Valid padding, stride 1, NCHW —
matching the loop-nest semantics of ``repro.core.frontend.conv2d``.

The wrapper lowers the conv to one 2-D contraction in one of three forms,
chosen by :func:`conv_form` from the conv's shape alone:

* ``"pointwise"`` — a 1×1 conv, laid out channel-major: the weights ``(Cout,
  Cin)`` times the ``(Cin, B·H·W)`` view of the input, whose pixels fill the
  lanes, giving the ``(Cout, B·H·W)`` output.  A caller that keeps its
  activations channel-major (the non-local block) passes that 2-D view
  itself and gets it back, with no transpose on either side; an NCHW input
  is transposed to it and back inside the call.

* ``"unrolled"`` — the loop nest unrolled onto the MXU, as OpenHLS unrolls
  it into the FPGA fabric: the input flattened per sample, ``(B,
  Cin·H·W)``, times the conv's unrolled (Toeplitz) weight matrix ``(Cin·H·W,
  Cout·Ho·Wo)``, which holds each weight where its tap meets its output
  pixel and zero elsewhere.  Input and output are the NCHW arrays
  themselves, flattened, and the output is lane-dense: no patch matrix and
  no transpose in HBM.  The matrix grows as (H·W)², so this form serves a
  conv with kh·kw > 1 whose matrix fits :data:`UNROLL_BYTES`.
* ``"im2col"`` — a conv with kh·kw > 1 and an image too large to unroll:
  the ``(B·Ho·Wo, Cin·kh·kw)`` patch matrix times the ``(Cin·kh·kw, Cout)``
  weight matrix.

Every form's kernel is ``relu?(lhs @ rhs + bias)`` per block, fp32
accumulation at ``HIGHEST``; the (wE,wF) quantiser runs on the streamed
operand in VMEM (the FloPoCo discipline: the rows of the lhs, or the
pointwise form's pixel columns of the rhs), and the weights are
quantised once per call before they are laid out (``quantize`` is
elementwise and maps ±0 to ±0, so the unrolled matrix's zeros stay zero).
A per-tap contraction over NCHW blocks would need in-kernel reshapes across
the lane axis (e.g. 81 lanes at img=11), which the TPU compiler refuses.

Grid: (rows / bm, cols / bn), columns innermost, so a row block is fetched
once and stays in VMEM while the weight blocks pass; where the whole
weight matrix is one column block it stays resident for the whole call.
The pointwise form's grid is (cols / bn,): its whole weight matrix stays
resident and the pixel columns stream.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.precision import FloatFormat, quantize
from repro.kernels.smallfloat_matmul.smallfloat_matmul import (
    _dot, _quantize_block)

#: the largest unrolled weight matrix a conv takes, in bytes of fp32.
#: BraggNN(s=4)'s conv2a, 5184 × 1568 (32.5 MB), is the largest it serves;
#: a 3×3 conv on a 64×64 image would need 63 MB for one channel in and out
UNROLL_BYTES = 48 << 20

#: VMEM that one grid step's blocks may take (lhs, rhs, bias and output,
#: each double-buffered, plus the quantiser's temporaries on the lhs
#: block), within v5e's 16 MiB scoped default
VMEM_BLOCK_BYTES = 12 << 20


def conv_form(cin: int, h: int, w: int, cout: int, kh: int, kw: int) -> str:
    """``"pointwise"``, ``"unrolled"`` or ``"im2col"``: how ``conv2d_vmem``
    lowers a valid, stride-1 conv of this shape (a pure function of the
    shape)."""
    if kh * kw == 1:
        return "pointwise"
    ho, wo = h - kh + 1, w - kw + 1
    if 4 * cin * h * w * cout * ho * wo <= UNROLL_BYTES:
        return "unrolled"
    return "im2col"


def tap_index(h: int, w: int, kh: int, kw: int) -> np.ndarray:
    """Which tap joins input pixel ``(Y, X)`` to output pixel ``(y, x)``:
    an ``(H·W, Ho·Wo)`` int32 map holding ``i·kw + j`` where ``(Y, X) =
    (y + i, x + j)``, and ``kh·kw`` (the index of an appended zero)
    elsewhere."""
    ho, wo = h - kh + 1, w - kw + 1
    yy, xx = np.divmod(np.arange(h * w), w)
    y, x = np.divmod(np.arange(ho * wo), wo)
    i = yy[:, None] - y[None, :]
    j = xx[:, None] - x[None, :]
    inside = (i >= 0) & (i < kh) & (j >= 0) & (j < kw)
    return np.where(inside, i * kw + j, kh * kw).astype(np.int32)


def unroll_weights(w: jax.Array, h: int, wdim: int) -> jax.Array:
    """(Cout, Cin, kh, kw) -> the ``(Cin·H·W, Cout·Ho·Wo)`` unrolled matrix
    ``T[(ci, y+i, x+j), (co, y, x)] = w[co, ci, i, j]``, zero elsewhere.

    Built by indexing alone (each ``(ci, co)`` block gathers its taps
    through :func:`tap_index`), so it holds ``w``'s entries bit for bit."""
    cout, cin, kh, kw = w.shape
    taps = jnp.concatenate([w.reshape(cout, cin, kh * kw),
                            jnp.zeros((cout, cin, 1), w.dtype)], axis=2)
    t = taps[:, :, tap_index(h, wdim, kh, kw)]    # (Cout, Cin, H·W, Ho·Wo)
    return t.transpose(1, 2, 0, 3).reshape(cin * h * wdim, -1)


def im2col(x: jax.Array, kh: int, kw: int) -> jax.Array:
    """(B, Cin, H, W) -> (B·Ho·Wo, Cin·kh·kw) valid-conv patch matrix.

    Column order is ``(cin, i, j)``, matching ``w.reshape(Cout, -1)``.
    """
    b, cin, h, wdim = x.shape
    ho, wo = h - kh + 1, wdim - kw + 1
    taps = [x[:, :, i:i + ho, j:j + wo] for i in range(kh) for j in range(kw)]
    p = jnp.stack(taps, axis=2)                   # (B, Cin, kh·kw, Ho, Wo)
    return p.transpose(0, 3, 4, 1, 2).reshape(b * ho * wo, cin * kh * kw)


class Blocks(NamedTuple):
    """The contraction's blocks: ``bm`` rows and ``bn`` columns per grid
    step, the columns padded to ``np_``."""

    bm: int
    bn: int
    np_: int


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _sublanes(n: int) -> int:
    return -(-n // 8) * 8


def blocks(rows: int, k: int, n: int, *, bm: int,
           quantised: bool) -> Blocks:
    """The blocks a ``(rows, k) @ (k, n)`` contraction takes: the whole
    ``n`` as one block where it fits :data:`VMEM_BLOCK_BYTES`, else blocks
    of 128 columns; the row block halved (to 8 at least) until a step fits.
    """
    def step_bytes(bm_, bn_):
        lhs = bm_ * _lanes(k)
        tmp = 3 * lhs if quantised else 0
        return 4 * (2 * (lhs + k * _lanes(bn_) + 8 * _lanes(bn_)
                         + bm_ * _lanes(bn_)) + tmp)

    bm = min(bm, rows)
    bn = n if step_bytes(min(bm, 128), n) <= VMEM_BLOCK_BYTES else 128
    while bm > 8 and step_bytes(bm, bn) > VMEM_BLOCK_BYTES:
        bm = max(8, bm // 2)
    return Blocks(bm, bn, -(-n // bn) * bn)


def pointwise_blocks(cin: int, cout: int, n: int, *, quantised: bool,
                     cap: Optional[int] = None) -> Blocks:
    """The blocks of the pointwise form's ``(Cout, Cin) @ (Cin, n)``: the
    ``Cout`` rows whole (``bm``) and ``bn`` pixel columns per grid step.

    ``bn`` is the whole ``n`` where it fits :data:`VMEM_BLOCK_BYTES` (and
    ``cap`` columns, if given); else a multiple of 128 within both, the
    largest that divides ``n`` if one lies in the upper half of that room,
    so the columns need no padding (the offline batch's 4096·81), and the
    room itself, with ``n`` padded to whole blocks, where none does."""
    def step_bytes(bn_):
        x = _sublanes(cin) * bn_
        out = _sublanes(cout) * bn_
        tmp = out + (3 * x if quantised else 0)
        fixed = _sublanes(cout) * (_lanes(cin) + 128)
        return 4 * (2 * (x + out + fixed) + tmp)

    per_col = step_bytes(1) - step_bytes(0)
    room = (VMEM_BLOCK_BYTES - step_bytes(0)) // per_col // 128 * 128
    if cap is not None:
        room = min(room, cap // 128 * 128)
    room = max(room, 128)
    if n <= room:
        return Blocks(cout, n, n)
    bn = next((c for c in range(room, room // 2, -128) if n % c == 0), room)
    return Blocks(cout, bn, -(-n // bn) * bn)


def _kernel(lhs_ref, rhs_ref, b_ref, o_ref, *, fmt, fuse_relu, streamed=0):
    """``relu?(lhs @ rhs + b)`` on one block.  The quantiser rounds the
    streamed operand (``streamed``: 0 the lhs rows, 1 the rhs pixel
    columns); the weights, the other, come pre-quantised."""
    ops = [lhs_ref[...].astype(jnp.float32), rhs_ref[...].astype(jnp.float32)]
    if fmt is not None:
        ops[streamed] = _quantize_block(ops[streamed], *fmt)
    acc = _dot(*ops)
    if b_ref is not None:
        acc = acc + b_ref[...]
    if fuse_relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc


def _kernel_nobias(lhs_ref, rhs_ref, o_ref, **kw):
    _kernel(lhs_ref, rhs_ref, None, o_ref, **kw)


@functools.partial(jax.jit, static_argnames=(
    "fmt", "fuse_relu", "bm", "interpret"))
def conv2d_vmem(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                *, fmt: Optional[tuple[int, int]] = None,
                fuse_relu: bool = False, bm: Optional[int] = None,
                interpret: bool = False) -> jax.Array:
    """x: (B, Cin, H, W), w: (Cout, Cin, kh, kw), b: (Cout,) -> fp32
    (B, Cout, Ho, Wo).  For a 1×1 conv, x may instead be the channel-major
    ``(Cin, N)`` view of N pixels, and the result is then ``(Cout, N)``.

    ``bm`` caps the rows per grid step (samples in the unrolled form,
    patch rows in im2col, 512 by default) or the pixel columns (the
    pointwise form, as many as fit VMEM by default); rows or columns are
    zero-padded up to a whole number of blocks when there is more than one
    block.
    """
    cout, cin, kh, kw = w.shape
    if (x.shape[0] if x.ndim == 2 else x.shape[1]) != cin:
        raise ValueError(f"input channels differ: x {x.shape}, w {w.shape}")
    w = w.astype(jnp.float32)
    if fmt is not None:
        w = quantize(w, FloatFormat(*fmt))
    if b is not None:
        b = b.astype(jnp.float32)
    if x.ndim == 2:
        if kh * kw != 1:
            raise ValueError(f"a channel-major (Cin, N) input needs a 1×1 "
                             f"conv, got w {w.shape}")
        return _pointwise(x, w.reshape(cout, cin), b, fmt=fmt,
                          fuse_relu=fuse_relu, cap=bm, interpret=interpret)
    bsz, _, h, wdim = x.shape
    ho, wo = h - kh + 1, wdim - kw + 1
    form = conv_form(cin, h, wdim, cout, kh, kw)
    if form == "pointwise":
        xc = x.transpose(1, 0, 2, 3).reshape(cin, bsz * h * wdim)
        out = _pointwise(xc, w.reshape(cout, cin), b, fmt=fmt,
                         fuse_relu=fuse_relu, cap=bm, interpret=interpret)
        return out.reshape(cout, bsz, h, wdim).transpose(1, 0, 2, 3)
    if form == "unrolled":
        lhs = x.reshape(bsz, cin * h * wdim)
        rhs = unroll_weights(w, h, wdim)
        if b is not None:
            b = jnp.repeat(b, ho * wo)
    else:
        lhs = im2col(x, kh, kw)
        rhs = w.reshape(cout, cin * kh * kw).T
    rows, kdim = lhs.shape
    n = rhs.shape[1]
    blk = blocks(rows, kdim, n, bm=512 if bm is None else bm,
                 quantised=fmt is not None)
    rows_p = -(-rows // blk.bm) * blk.bm
    if rows_p != rows:
        lhs = jnp.pad(lhs, ((0, rows_p - rows), (0, 0)))
    if blk.np_ != n:
        rhs = jnp.pad(rhs, ((0, 0), (0, blk.np_ - n)))
        b = None if b is None else jnp.pad(b, (0, blk.np_ - n))

    in_specs = [
        pl.BlockSpec((blk.bm, kdim), lambda i, j: (i, 0)),
        pl.BlockSpec((kdim, blk.bn), lambda i, j: (0, j)),
    ]
    args = [lhs, rhs]
    kernel = _kernel_nobias
    if b is not None:
        # bias kept 2-D: TPU VMEM tiles are (sublane, lane)-shaped
        in_specs.append(pl.BlockSpec((1, blk.bn), lambda i, j: (0, j)))
        args.append(b.reshape(1, blk.np_))
        kernel = _kernel
    out = pl.pallas_call(
        functools.partial(kernel, fmt=fmt, fuse_relu=fuse_relu),
        grid=(rows_p // blk.bm, blk.np_ // blk.bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk.bm, blk.bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows_p, blk.np_), jnp.float32),
        interpret=interpret,
    )(*args)
    if (rows_p, blk.np_) != (rows, n):
        out = out[:rows, :n]
    if form == "unrolled":
        return out.reshape(bsz, cout, ho, wo)
    return out.reshape(bsz, ho, wo, cout).transpose(0, 3, 1, 2)


def _pointwise(x, w, b, *, fmt, fuse_relu, cap, interpret):
    """The pointwise form: ``(Cout, Cin) @ (Cin, N) -> (Cout, N)`` with the
    weights (quantised already) resident and the pixel columns streamed."""
    cin, n = x.shape
    cout = w.shape[0]
    blk = pointwise_blocks(cin, cout, n, quantised=fmt is not None, cap=cap)
    if blk.np_ != n:
        x = jnp.pad(x, ((0, 0), (0, blk.np_ - n)))
    in_specs = [
        pl.BlockSpec((cout, cin), lambda j: (0, 0)),
        pl.BlockSpec((cin, blk.bn), lambda j: (0, j)),
    ]
    args = [w, x]
    kernel = _kernel_nobias
    if b is not None:
        # bias as a column: it broadcasts along the pixel lanes
        in_specs.append(pl.BlockSpec((cout, 1), lambda j: (0, 0)))
        args.append(b.reshape(cout, 1))
        kernel = _kernel
    out = pl.pallas_call(
        functools.partial(kernel, fmt=fmt, fuse_relu=fuse_relu, streamed=1),
        grid=(blk.np_ // blk.bn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((cout, blk.bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((cout, blk.np_), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:, :n] if blk.np_ != n else out
