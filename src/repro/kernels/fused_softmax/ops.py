"""Public wrapper for the fused softmax kernel."""

from __future__ import annotations

import jax

from repro.kernels.fused_softmax.fused_softmax import fused_softmax
from repro.kernels.fused_softmax.ref import fused_softmax_ref


def softmax(x: jax.Array, *, taylor_order: int = 0, range_reduce: int = 2,
            use_pallas: bool = False, interpret: bool = False) -> jax.Array:
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    if use_pallas:
        out = fused_softmax(x2, taylor_order=taylor_order,
                            range_reduce=range_reduce, interpret=interpret)
    else:
        out = fused_softmax_ref(x2, taylor_order=taylor_order,
                                range_reduce=range_reduce)
    return out.reshape(orig_shape)
