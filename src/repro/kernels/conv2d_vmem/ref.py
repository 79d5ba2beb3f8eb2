"""Pure-jnp oracle for conv2d_vmem (valid padding, stride 1, NCHW, or the
channel-major ``(Cin, N)`` view of N pixels for a 1×1 conv)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.precision import FloatFormat, quantize


def conv2d_ref(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, *,
               fmt: Optional[tuple[int, int]] = None,
               fuse_relu: bool = False) -> jax.Array:
    if x.ndim == 2:   # channel-major (Cin, N): one 1×N image
        return conv2d_ref(x[None, :, None, :], w, b, fmt=fmt,
                          fuse_relu=fuse_relu)[0, :, 0, :]
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fmt is not None:
        ff = FloatFormat(*fmt)
        x = quantize(x, ff)
        w = quantize(w, ff)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if b is not None:
        out = out + b.astype(jnp.float32)[None, :, None, None]
    if fuse_relu:
        out = jnp.maximum(out, 0.0)
    return out
