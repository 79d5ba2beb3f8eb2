"""Pallas TPU kernel: fused row softmax with optional Taylor-series exp.

One grid step owns a block of rows; max-subtraction, exponentiation and
normalisation happen in a single VMEM residency (the paper's reduction-tree
softmax as one fused unit — §3.2 item 4 + §4.1 soft_max).  ``taylor_order``
> 0 switches exp to the paper's k-th-order Taylor expansion with 2^r range
reduction, matching the scalar-DFG functional model bit-for-bit in intent.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _taylor_exp(x, order: int, range_reduce: int):
    y = x / float(1 << range_reduce)
    acc = jnp.ones_like(y)
    term = jnp.ones_like(y)
    for k in range(1, order + 1):
        term = term * y / float(k)
        acc = acc + term
    for _ in range(range_reduce):
        acc = acc * acc
    return acc


def _softmax_kernel(x_ref, o_ref, *, taylor_order, range_reduce):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    z = x - m
    if taylor_order:
        e = _taylor_exp(z, taylor_order, range_reduce)
    else:
        e = jnp.exp(z)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "taylor_order", "range_reduce", "block_rows", "interpret"))
def fused_softmax(x: jax.Array, *, taylor_order: int = 0,
                  range_reduce: int = 2, block_rows: int = 256,
                  interpret: bool = False) -> jax.Array:
    """Softmax over the last axis of a 2-D array (rows, cols).

    Up to ``block_rows`` rows run as one block.  More rows are zero-padded
    up to a whole number of blocks (a padded row is a harmless uniform
    softmax) and the padding is sliced off, so every row count lowers: the
    NLB's B·81 rows at img=11 are a multiple of no TPU-aligned block.
    """
    rows, cols = x.shape
    block_rows = min(block_rows, rows)
    rows_p = -(-rows // block_rows) * block_rows
    xp = jnp.pad(x, ((0, rows_p - rows), (0, 0))) if rows_p != rows else x
    out = pl.pallas_call(
        functools.partial(_softmax_kernel, taylor_order=taylor_order,
                          range_reduce=range_reduce),
        grid=(rows_p // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, cols), x.dtype),
        interpret=interpret,
    )(xp)
    return out[:rows] if rows_p != rows else out
