"""Public wrapper for the weights-in-VMEM conv kernel."""

from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.conv2d_vmem.conv2d_vmem import conv2d_vmem
from repro.kernels.conv2d_vmem.ref import conv2d_ref


def conv2d(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, *,
           fmt: Optional[tuple[int, int]] = None, fuse_relu: bool = False,
           use_pallas: bool = False, interpret: bool = False) -> jax.Array:
    if use_pallas:
        return conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=fuse_relu,
                           interpret=interpret)
    return conv2d_ref(x, w, b, fmt=fmt, fuse_relu=fuse_relu)
