"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
initialisation, and smoke tests/benches must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 (two pods, 512 chips).

    The "pod" axis is outermost: only data-parallel gradient reduction (or,
    opt-in, pipeline activations) crosses the slow inter-pod links — the
    paper's SLR-crossing discipline applied to pods.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for CI (requires >= prod(shape) visible devices)."""
    return _mesh(shape, axes)


def single_device_mesh() -> Mesh:
    return _mesh((1, 1), ("data", "model"))
