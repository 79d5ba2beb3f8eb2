"""Public wrapper: GQA-aware flash attention over (B, S, H, D) tensors."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              logit_cap: float = 0.0, use_pallas: bool = False,
              interpret: bool = False) -> jax.Array:
    """q: (B, S, H, D), k/v: (B, S, K, D) with H % K == 0."""
    b, s, h, d = q.shape
    n_kv = k.shape[2]
    g = h // n_kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, -1, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, -1, d)
    fn = flash_attention if use_pallas else flash_attention_ref
    kw = {"interpret": interpret} if use_pallas else {}
    of = fn(qf, kf, vf, causal=causal, window=window, logit_cap=logit_cap,
            **kw)
    return of.reshape(b, h, s, d).transpose(0, 2, 1, 3)
