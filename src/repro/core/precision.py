"""FloPoCo-style reduced-precision floating point emulation (paper §3, §4.2).

OpenHLS delegates arithmetic to FloPoCo-generated cores parameterised by
(wE, wF) = (exponent bits, fraction bits).  FloPoCo's representation differs
from IEEE-754: **no subnormals** (values below the smallest normal flush to
zero) and two extra exception bits instead of reserved exponent codes, so a
(wE, wF) number occupies  1 + wE + wF + 2  wires — e.g. (5,4) is 12 bits,
which is exactly the width used in the paper's SLL-crossing computation
(§4.2: (1x16x9x9 + 1x8x9x9) x 12 = 23,328 > 23,040 SLLs).

We emulate the value lattice of these formats inside fp32 containers:
round-to-nearest-even on the fraction, exponent clamping with flush-to-zero
below ``emin`` and saturation above ``emax``.  A straight-through-estimator
wrapper makes the quantiser differentiable for quantisation-aware training.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FloatFormat:
    """A FloPoCo (wE, wF) floating-point format."""

    exp_bits: int
    man_bits: int
    name: str = ""

    @property
    def bias(self) -> int:
        return (1 << (self.exp_bits - 1)) - 1

    @property
    def emax(self) -> int:
        return self.bias

    @property
    def emin(self) -> int:
        return 1 - self.bias

    @property
    def max_value(self) -> float:
        return float((2.0 - 2.0 ** (-self.man_bits)) * 2.0 ** self.emax)

    @property
    def min_normal(self) -> float:
        return float(2.0 ** self.emin)

    @property
    def wire_bits(self) -> int:
        """Bits on a wire: sign + wE + wF + 2 exception bits (paper §4.2)."""
        return 1 + self.exp_bits + self.man_bits + 2

    def __str__(self) -> str:
        return self.name or f"({self.exp_bits},{self.man_bits})"


#: The three formats the paper steps through for BraggNN.
FP_5_11 = FloatFormat(5, 11, "(5,11)")   # ~IEEE half precision
FP_5_4 = FloatFormat(5, 4, "(5,4)")
FP_5_3 = FloatFormat(5, 3, "(5,3)")
FORMATS = {"5_11": FP_5_11, "5_4": FP_5_4, "5_3": FP_5_3}


def _pow2(e, xp):
    """Exact ``2**e`` for integer ``e``, built from fp32 exponent bits.

    XLA's ``exp2`` is not exact on every backend (on the CPU it misses
    integer powers by an ulp), which moves quantised values off the
    lattice.  ``e`` is clamped to the fp32 normal range; every value whose
    exponent lies outside it is flushed or saturated by the caller.
    """
    bits = (xp.clip(e, -126, 127) + 127).astype(xp.int32) << 23
    if xp is np:
        return bits.view(np.float32)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _quantize_generic(x, fmt: FloatFormat, xp):
    """Shared numpy/jnp quantiser.  RNE fraction rounding, FTZ, saturation.

    Written with operations the Pallas TPU compiler lowers, so kernels
    call it on VMEM blocks too.
    """
    x = xp.asarray(x, dtype=xp.float32)
    sign = xp.sign(x)
    v = xp.abs(x)
    # decompose |x| = f * 2^E with f in [0.5, 1)  ->  m = 2f in [1, 2)
    f, e = xp.frexp(v)
    m = f * 2.0
    e = e - 1
    # round-to-nearest-even on the fraction
    scale = float(1 << fmt.man_bits)
    q = xp.round((m - 1.0) * scale)
    carry = q >= scale
    m_q = xp.where(carry, 1.0, 1.0 + q / scale)
    e_q = xp.where(carry, e + 1, e)
    out = sign * m_q * _pow2(e_q, xp)
    # flush-to-zero below min normal (FloPoCo: no subnormals)
    out = xp.where(v < fmt.min_normal * 0.5, 0.0, out)
    out = xp.where((v >= fmt.min_normal * 0.5) & (v < fmt.min_normal),
                   sign * fmt.min_normal, out)
    # saturate above max finite (FloPoCo raises the overflow exception bit;
    # we saturate, which is the DNN-friendly policy — noted in DESIGN.md)
    out = xp.where(v > fmt.max_value, sign * fmt.max_value, out)
    # exact zeros / non-finites pass through
    out = xp.where(v == 0.0, x, out)
    out = xp.where(v <= np.finfo(np.float32).max, out, x)
    return out


def quantize_np(x: np.ndarray, fmt: FloatFormat) -> np.ndarray:
    """Numpy quantiser — used by the scalar-DFG functional models."""
    return _quantize_generic(x, fmt, np).astype(np.float32)


def quantize(x: jax.Array, fmt: FloatFormat) -> jax.Array:
    """jnp quantiser — used by the tensor-level production path."""
    return _quantize_generic(x, fmt, jnp)


@jax.custom_vjp
def ste_quantize(x: jax.Array, exp_bits: int, man_bits: int) -> jax.Array:
    """Quantise with a straight-through gradient (for QAT of BraggNN)."""
    return quantize(x, FloatFormat(int(exp_bits), int(man_bits)))


def _ste_fwd(x, exp_bits, man_bits):
    return ste_quantize(x, exp_bits, man_bits), None


def _ste_bwd(_, g):
    return (g, None, None)


ste_quantize.defvjp(_ste_fwd, _ste_bwd)


def quantize_tree(tree, fmt: FloatFormat):
    """Quantise every leaf of a parameter pytree (weights-to-registers)."""
    return jax.tree_util.tree_map(
        lambda x: quantize(x, fmt) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def exponent_histogram(tree) -> dict[int, int]:
    """Histogram of weight exponents (paper Fig. 7) over a parameter tree."""
    hist: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.asarray(leaf, dtype=np.float32).ravel()
        arr = arr[np.isfinite(arr) & (arr != 0.0)]
        if arr.size == 0:
            continue
        _, e = np.frexp(np.abs(arr))
        e = e - 1
        vals, counts = np.unique(e, return_counts=True)
        for v, c in zip(vals.tolist(), counts.tolist()):
            hist[int(v)] = hist.get(int(v), 0) + int(c)
    return hist


def required_exponent_bits(hist: dict[int, int], coverage: float = 1.0) -> int:
    """Smallest wE covering ``coverage`` of the exponent mass (Fig. 7 logic)."""
    if not hist:
        return 1
    total = sum(hist.values())
    items = sorted(hist.items(), key=lambda kv: -kv[1])
    kept: list[int] = []
    acc = 0
    for e, c in items:
        kept.append(e)
        acc += c
        if acc >= coverage * total:
            break
    lo, hi = min(kept), max(kept)
    for we in range(2, 12):
        fmt = FloatFormat(we, 1)
        if fmt.emin <= lo and hi <= fmt.emax:
            return we
    return 12
