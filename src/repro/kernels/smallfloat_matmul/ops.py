"""Public jit'd wrapper for the smallfloat matmul kernel.

``use_pallas=False`` routes to the oracle; ``use_pallas=True`` routes to
the kernel, compiled for the TPU unless ``interpret=True``.
"""

from __future__ import annotations

import jax

from repro.kernels.smallfloat_matmul.ref import smallfloat_matmul_ref
from repro.kernels.smallfloat_matmul.smallfloat_matmul import smallfloat_matmul


def matmul(x: jax.Array, w: jax.Array, b=None, *, exp_bits=5,
           man_bits=4, fuse_relu: bool = False,
           use_pallas: bool = False, interpret: bool = False) -> jax.Array:
    """``exp_bits=None`` skips operand quantisation (plain fp32 matmul)."""
    if use_pallas:
        return smallfloat_matmul(x, w, b, exp_bits=exp_bits,
                                 man_bits=man_bits, fuse_relu=fuse_relu,
                                 interpret=interpret)
    return smallfloat_matmul_ref(x, w, b, exp_bits=exp_bits,
                                 man_bits=man_bits, fuse_relu=fuse_relu)
