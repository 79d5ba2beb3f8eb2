"""Pallas TPU kernel: flash attention (causal / local-window / softcap).

Grid: (batch*kv_heads*groups, Sq/bq, Skv/bk) with the KV dimension
innermost.  Running (max, denom, accumulator) live in VMEM scratch across
KV steps; the output block is written once on the final KV step.  This is
the per-chip twin of the pure-JAX ``repro.nn.attention.blockwise_attention``
(which remains the XLA fallback the dry-run lowers): same math, same
masking contract, validated against the same oracle.

The fully static schedule — every (q-block, kv-block) pair visited at a
fixed grid step, no dynamic control flow — is the paper's "fully scheduled
design" discipline at kernel granularity.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, logit_cap, bq, bk, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)           # (bq, d)
    k = k_ref[0].astype(jnp.float32)           # (bk, d)
    v = v_ref[0].astype(jnp.float32)           # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None and window > 0:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_blk)
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(s == NEG_INF, 0.0, p)
    corr = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_safe))
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "logit_cap", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    logit_cap: float = 0.0, bq: int = 256, bk: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, D), k/v: (BH, Skv, D) — heads pre-flattened into BH.

    GQA is expressed by repeating kv head indices in the caller (ops.py).
    """
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    n_k = skv // bk
    grid = (bh, sq // bq, n_k)
    scale = 1.0 / (d ** 0.5)

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, logit_cap=logit_cap, bq=bq, bk=bk,
                          n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),     # running max
            pltpu.VMEM((bq,), jnp.float32),     # running denominator
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
