"""Pallas TPU kernel: blocked matmul with on-the-fly FloPoCo (wE,wF)
quantisation of both operands, fp32 MXU accumulation, optional fused bias
and ReLU.

This is the TPU rendering of the paper's reduced-precision MAC array
(§4.2): operands are rounded to the (wE,wF) lattice *in VMEM* immediately
before hitting the MXU, exactly as FloPoCo cores consume reduced-precision
inputs, and the accumulator stays wide (fp32) like the DSP48 accumulator.

Grid: (M/bm, N/bn, K/bk), K innermost; the output block is revisited across
the K dimension and accumulated in place (init at k==0), the canonical TPU
matmul schedule.  Block shapes default to MXU-aligned (128, 128, 128), each
cut to its dimension where that is smaller.  Any K and N lower
(:func:`blocking`): a dimension that its block does not divide is taken
whole as one block, which Mosaic accepts at any extent (BraggNN(s=4)'s
first dense layer contracts over K = 200); only where a whole block would
not fit VMEM is the dimension zero-padded up to whole blocks instead, which
is exact because ``quantize(0) = 0``.  Rows are always padded.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import FloatFormat, quantize


def _quantize_block(x, exp_bits, man_bits):
    """(wE,wF) quantisation of a VMEM block: ``precision.quantize`` itself.

    ``exp_bits=None`` means full fp32 — the identity — so one kernel serves
    both the reduced-precision MAC array and the plain fp32 fast path.
    """
    if exp_bits is None:
        return x
    return quantize(x, FloatFormat(exp_bits, man_bits))


def _dot(x, w):
    """fp32 MXU contraction at full fp32 precision (the TPU's default f32
    matmul rounds operands to bf16)."""
    return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


#: VMEM that one grid step's blocks may take (x, w, bias and output, each
#: double-buffered) before a dimension is padded rather than taken whole;
#: a quarter of v5e's 16 MiB scoped default, leaving the rest to the
#: quantiser's temporaries
VMEM_BLOCK_BYTES = 4 << 20


class Blocking(NamedTuple):
    """How a (K, N) weight is blocked: the K and N blocks, K and N padded
    to whole blocks, and a tag: ``"tiled"`` where the blocks divide K and
    N, else the dimensions taken whole or padded (``"wholek"``,
    ``"padn"``, ``"wholek:wholen"``, ...)."""

    bk: int
    bn: int
    kp: int
    np_: int
    tag: str


def blocking(k: int, n: int, *, bm: int = 128, bn: int = 128,
             bk: int = 128) -> Blocking:
    """The blocks ``smallfloat_matmul`` uses for a (K, N) weight.  It does
    not depend on the rows: the VMEM check assumes a full ``bm`` block."""
    bk, bn = min(bk, k), min(bn, n)

    def fits(bk_, bn_):
        return 2 * 4 * (bm * bk_ + bk_ * bn_ + bn_ + bm * bn_) \
            <= VMEM_BLOCK_BYTES

    tags = []
    if k % bk:
        bk = k if fits(k, bn) else bk
        tags.append("wholek" if bk == k else "padk")
    if n % bn:
        bn = n if fits(bk, n) else bn
        tags.append("wholen" if bn == n else "padn")
    return Blocking(bk, bn, -(-k // bk) * bk, -(-n // bn) * bn,
                    ":".join(tags) or "tiled")


def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, *, exp_bits, man_bits,
                   fuse_relu, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = _quantize_block(x_ref[...].astype(jnp.float32), exp_bits, man_bits)
    w = _quantize_block(w_ref[...].astype(jnp.float32), exp_bits, man_bits)
    o_ref[...] += _dot(x, w)

    @pl.when(k == n_k - 1)
    def _finish():
        acc = o_ref[...]
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        if fuse_relu:
            acc = jnp.maximum(acc, 0.0)
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=(
    "exp_bits", "man_bits", "fuse_relu", "bm", "bn", "bk", "interpret"))
def smallfloat_matmul(x: jax.Array, w: jax.Array, b=None, *,
                      exp_bits: int = 5, man_bits: int = 4,
                      fuse_relu: bool = False, bm: int = 128, bn: int = 128,
                      bk: int = 128, interpret: bool = False) -> jax.Array:
    """x: (M, K), w: (K, N), b: (N,) or None  ->  (M, N) fp32.

    Rows are zero-padded up to a whole number of ``bm`` blocks and the
    padding is sliced off, so any batch size lowers; K and N are blocked
    as :func:`blocking` says.
    """
    m, kdim = x.shape
    k2, n = w.shape
    if kdim != k2:
        raise ValueError(f"contraction dims differ: x {x.shape}, w {w.shape}")
    blk = blocking(kdim, n, bm=bm, bn=bn, bk=bk)
    bn, bk, kp, np_ = blk.bn, blk.bk, blk.kp, blk.np_
    bm = min(bm, m)
    mp = -(-m // bm) * bm
    if (mp, kp) != (m, kdim):
        x = jnp.pad(x, ((0, mp - m), (0, kp - kdim)))
    if (kp, np_) != (kdim, n):
        w = jnp.pad(w, ((0, kp - kdim), (0, np_ - n)))
        b = None if b is None else jnp.pad(b, (0, np_ - n))
    n_k = kp // bk
    grid = (mp // bm, np_ // bn, n_k)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if b is not None:
        # bias kept 2-D: TPU VMEM tiles are (sublane, lane)-shaped
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(b.reshape(1, np_))

    kernel = functools.partial(
        _matmul_kernel if b is not None else _matmul_kernel_nobias,
        exp_bits=exp_bits, man_bits=man_bits, fuse_relu=fuse_relu, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:m, :n] if (mp, np_) != (m, n) else out


def _matmul_kernel_nobias(x_ref, w_ref, o_ref, **kw):
    _matmul_kernel(x_ref, w_ref, None, o_ref, **kw)
