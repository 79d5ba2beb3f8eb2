"""Pallas TPU kernel: blocked matmul with on-the-fly FloPoCo (wE,wF)
quantisation of both operands, fp32 MXU accumulation, optional fused bias
and ReLU.

This is the TPU rendering of the paper's reduced-precision MAC array
(§4.2): operands are rounded to the (wE,wF) lattice *in VMEM* immediately
before hitting the MXU, exactly as FloPoCo cores consume reduced-precision
inputs, and the accumulator stays wide (fp32) like the DSP48 accumulator.

Grid: (M/bm, N/bn, K/bk), K innermost; the output block is revisited across
the K dimension and accumulated in place (init at k==0), the canonical TPU
matmul schedule.  Block shapes default to MXU-aligned (128, 128, 128).
"""

from __future__ import annotations

import functools

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
    padding is sliced off, so any batch size lowers.
    """
    m, kdim = x.shape
    k2, n = w.shape
    if kdim != k2:
        raise ValueError(f"contraction dims differ: x {x.shape}, w {w.shape}")
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, kdim)
    if n % bn or kdim % bk:
        raise ValueError(f"N and K must tile evenly: (N, K) = {(n, kdim)}, "
                         f"blocks {(bn, bk)}")
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    n_k = kdim // bk
    grid = (mp // bm, n // bn, n_k)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if b is not None:
        # bias kept 2-D: TPU VMEM tiles are (sublane, lane)-shaped
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(b.reshape(1, n))

    kernel = functools.partial(
        _matmul_kernel if b is not None else _matmul_kernel_nobias,
        exp_bits=exp_bits, man_bits=man_bits, fuse_relu=fuse_relu, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:m] if mp != m else out


def _matmul_kernel_nobias(x_ref, w_ref, o_ref, **kw):
    _matmul_kernel(x_ref, w_ref, None, o_ref, **kw)
