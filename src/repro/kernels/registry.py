"""Kernel registry — the catalogue ``emit_pallas`` lowers through.

Two tables:

* :data:`KERNELS` — the four hand-written Pallas exemplars, registered as
  pattern-matched fast paths for the loop nests the nn bridge emits
  (``Conv2d`` -> conv2d_vmem, ``Linear`` -> smallfloat_matmul,
  ``Softmax`` / the NLB attention softmax -> fused_softmax, the whole NLB
  attention core -> flash_attention).  Each entry carries the unified
  wrapper (the oracle by default; ``use_pallas=True`` routes to the
  ``pl.pallas_call`` kernel, interpreted with ``interpret=True``), the raw
  kernel, and the pure-jnp oracle, so callers pick the execution mode
  without knowing the module layout.

* :data:`OPCODE_KERNELS` — the scalar-DFG opcode -> vectorised jnp compute
  table used by the generic tier: contiguous runs of levelised
  (level, opcode) groups whose opcodes all appear here are fused into one
  compiled segment; a group whose opcode is missing falls back to the
  plain tensor path (and is recorded in the ``PallasPlan``).

Registration is open: ``register()`` accepts new entries (e.g. a
transformer-block kernel) without touching the emitter.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One registered kernel: unified wrapper + raw kernel + oracle."""

    name: str
    fn: Callable          #: unified wrapper (``use_pallas=``/``interpret=``)
    kernel: Callable      #: the raw ``pl.pallas_call`` implementation
    oracle: Callable      #: the pure-jnp reference
    accelerates: tuple[str, ...]   #: nn-graph node/nest patterns served
    description: str = ""


KERNELS: dict[str, KernelEntry] = {}


def register(entry: KernelEntry) -> KernelEntry:
    if entry.name in KERNELS:
        raise ValueError(f"kernel {entry.name!r} already registered")
    KERNELS[entry.name] = entry
    return entry


def get(name: str) -> KernelEntry:
    try:
        return KERNELS[name]
    except KeyError:
        raise KeyError(f"no kernel {name!r}; registered: "
                       f"{sorted(KERNELS)}") from None


def names() -> list[str]:
    return sorted(KERNELS)


def for_pattern(pattern: str) -> Optional[KernelEntry]:
    """The registered fast path for an nn-graph pattern name, if any."""
    for entry in KERNELS.values():
        if pattern in entry.accelerates:
            return entry
    return None


def _register_exemplars() -> None:
    from repro.kernels.conv2d_vmem import conv2d_vmem as _conv_mod
    from repro.kernels.conv2d_vmem import ops as _conv_ops
    from repro.kernels.conv2d_vmem import ref as _conv_ref
    from repro.kernels.flash_attention import flash_attention as _fa_mod
    from repro.kernels.flash_attention import ops as _fa_ops
    from repro.kernels.flash_attention import ref as _fa_ref
    from repro.kernels.fused_softmax import fused_softmax as _sm_mod
    from repro.kernels.fused_softmax import ops as _sm_ops
    from repro.kernels.fused_softmax import ref as _sm_ref
    from repro.kernels.smallfloat_matmul import ops as _mm_ops
    from repro.kernels.smallfloat_matmul import ref as _mm_ref
    from repro.kernels.smallfloat_matmul import \
        smallfloat_matmul as _mm_mod

    register(KernelEntry(
        name="conv2d_vmem",
        fn=_conv_ops.conv2d,
        kernel=_conv_mod.conv2d_vmem,
        oracle=_conv_ref.conv2d_ref,
        accelerates=("Conv2d", "nlb.conv1x1"),
        description="weights-resident valid conv as one contraction "
                    "(1x1 convs channel-major, small 3x3 convs unrolled, "
                    "the rest im2col), optional "
                    "fused ReLU + (wE,wF) operand quantisation"))
    register(KernelEntry(
        name="smallfloat_matmul",
        fn=_mm_ops.matmul,
        kernel=_mm_mod.smallfloat_matmul,
        oracle=_mm_ref.smallfloat_matmul_ref,
        accelerates=("Linear", "MLP", "Attention.proj"),
        description="blocked matmul, fp32 accumulate, optional (wE,wF) "
                    "operand quantisation + fused bias/ReLU"))
    register(KernelEntry(
        name="fused_softmax",
        fn=_sm_ops.softmax,
        kernel=_sm_mod.fused_softmax,
        oracle=_sm_ref.fused_softmax_ref,
        accelerates=("Softmax", "nlb.soft", "Attention.soft"),
        description="row softmax in one VMEM residency, incl. the paper's "
                    "Taylor-exp mode (matches the DFG functional model)"))
    register(KernelEntry(
        name="flash_attention",
        fn=_fa_ops.attention,
        kernel=_fa_mod.flash_attention,
        oracle=_fa_ref.flash_attention_ref,
        accelerates=("NonLocalBlock.attention", "Attention"),
        description="blockwise attention; NLB throughput mode "
                    "(true-exp softmax — not the Taylor functional model)"))


_register_exemplars()


# ---------------------------------------------------------------------------
# Generic tier: scalar-DFG opcode -> vectorised jnp compute
# ---------------------------------------------------------------------------

def _fmac(a):
    """``a0 * a1 + a2`` with the product rounded on its own, as
    ``emit.evaluate`` computes it.  XLA's CPU backend contracts a fused
    multiply-add into one rounding; the NaN-preserving select between the
    two ops keeps them apart."""
    import jax.numpy as jnp
    prod = a[0] * a[1]
    return jnp.where(jnp.isnan(prod), jnp.nan, prod) + a[2]


def _opcode_table():
    import jax.numpy as jnp

    return {
        # opcode -> (arity, compute over gathered operand vectors)
        "mulf": (2, lambda a: a[0] * a[1]),
        "addf": (2, lambda a: a[0] + a[1]),
        "subf": (2, lambda a: a[0] - a[1]),
        "divf": (2, lambda a: a[0] / a[1]),
        "sqrtf": (1, lambda a: jnp.sqrt(a[0])),
        "maxf": (2, lambda a: jnp.maximum(a[0], a[1])),
        "minf": (2, lambda a: jnp.minimum(a[0], a[1])),
        "negf": (1, lambda a: -a[0]),
        "relu": (1, lambda a: jnp.maximum(a[0], 0.0)),
        "fmac": (3, _fmac),
        "load": (1, lambda a: a[0]),
        "store": (1, lambda a: a[0]),
        "copy": (1, lambda a: a[0]),
        # cmpugt/select are deliberately absent: raw (un-recomposed) graphs
        # route those groups through the per-group tensor fallback, which
        # is exactly the path the fallback tests pin down.
    }


OPCODE_KERNELS = _opcode_table()

#: opcodes whose results the functional model does NOT re-quantise
#: (moves/compares — mirrors ``emit.evaluate``)
NO_QUANT_OPCODES = frozenset({"cmpugt", "load", "store", "copy"})
