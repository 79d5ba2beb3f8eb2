"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler (Mosaic for the kernels, XLA for the
program around them) compiles for a v5e described by its topology.  This
catches what interpret mode cannot — block shapes the TPU tiling refuses,
in-kernel reshapes across the lane axis, operations with no Mosaic
lowering — at BraggNN(img=11)'s real shapes, at s=1 and at s=4 (the
original widths).  Nothing runs, so these tests say nothing about results
or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and a test worker
that is not given this file must not try.
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core import emit_pallas  # noqa: E402
from repro.core.precision import FORMATS  # noqa: E402
from repro.kernels.conv2d_vmem.conv2d_vmem import conv2d_vmem  # noqa: E402
from repro.kernels.fused_softmax.fused_softmax import fused_softmax  # noqa: E402
from repro.kernels.smallfloat_matmul.smallfloat_matmul import (  # noqa: E402
    smallfloat_matmul)
from repro.models import braggnn  # noqa: E402

#: the batch the whole-program compile uses (the benchmark's µs/sample batch)
BATCH = 64

#: the batch of the benchmark's offline cells
OFFLINE_BATCH = 4096

#: BraggNN(s=1, img=11) convs: (name, x shape, w shape, has bias)
CONVS = [
    ("conv1", (BATCH, 1, 11, 11), (16, 1, 3, 3), True),
    ("nlb.theta", (BATCH, 16, 9, 9), (8, 16, 1, 1), False),
    ("nlb.out_cnn", (BATCH, 8, 9, 9), (16, 8, 1, 1), False),
    ("conv2a", (BATCH, 16, 9, 9), (8, 16, 3, 3), True),
    ("conv2b", (BATCH, 8, 7, 7), (2, 8, 3, 3), True),
    # s=4: 64 -> 32 -> 8 channels; conv2a's patch block is (512, 576)
    ("s4.conv1", (BATCH, 1, 11, 11), (64, 1, 3, 3), True),
    ("s4.nlb.theta", (BATCH, 64, 9, 9), (32, 64, 1, 1), False),
    ("s4.nlb.out_cnn", (BATCH, 32, 9, 9), (64, 32, 1, 1), False),
    ("s4.conv2a", (BATCH, 64, 9, 9), (32, 64, 3, 3), True),
    ("s4.conv2b", (BATCH, 32, 7, 7), (8, 32, 3, 3), True),
]

#: BraggNN dense layers, s=1 then s=4: (K, N); s=4's K = 200 is blocked
#: whole (no 128-block divides it)
DENSES = [(50, 16), (16, 8), (8, 4), (4, 2),
          (200, 64), (64, 32), (32, 16), (16, 2)]

FMTS = [None, (5, 4)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *args):
    """Compile ``fn`` for the described chip; ``args`` are shapes or
    pytrees of shapes.  Returns the compiled program's text."""
    specs = [jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding),
        a, is_leaf=lambda s: isinstance(s, tuple)) for a in args]
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("fmt", FMTS, ids=["fp32", "5_4"])
@pytest.mark.parametrize("name,xs,ws,bias", CONVS,
                         ids=[c[0] for c in CONVS])
def test_conv2d_vmem_compiles(one_chip, name, xs, ws, bias, fmt):
    if bias:
        def fn(x, w, b):
            return conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=True)
        text = _compile(fn, one_chip, xs, ws, (ws[0],))
    else:
        def fn(x, w):
            return conv2d_vmem(x, w, None, fmt=fmt)
        text = _compile(fn, one_chip, xs, ws)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fmt", FMTS, ids=["fp32", "5_4"])
@pytest.mark.parametrize("k,n", DENSES, ids=[f"{k}x{n}" for k, n in DENSES])
def test_smallfloat_matmul_compiles(one_chip, k, n, fmt):
    eb, mb = fmt if fmt is not None else (None, None)

    def fn(x, w, b):
        return smallfloat_matmul(x, w, b, exp_bits=eb, man_bits=mb,
                                 fuse_relu=True)
    text = _compile(fn, one_chip, (BATCH, k), (k, n), (n,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 4, 32, 64])
def test_fused_softmax_compiles(one_chip, batch):
    """The NLB softmax: B·81 rows of 81 scores at img=11 — a row count no
    TPU-aligned block divides for most B."""
    def fn(x):
        return fused_softmax(x, taylor_order=8)
    text = _compile(fn, one_chip, (batch * 81, 81))
    assert "tpu_custom_call" in text


def _lower_braggnn(s, fmt):
    """The nest-tier BraggNN(s, img=11) core, its bound module and plan."""
    m = braggnn.build(s, 11)
    module = m.bind(m.init_params(jax.random.PRNGKey(0)))
    fmt_obj = FORMATS[fmt] if fmt is not None else None
    fmt_tuple = ((fmt_obj.exp_bits, fmt_obj.man_bits)
                 if fmt_obj is not None else None)
    plan = emit_pallas.PallasPlan(mode="nests", use_pallas=True,
                                  interpret=False, fmt=fmt)
    core, weight_names, _ = emit_pallas._lower_module(
        module, fmt_obj=fmt_obj, fmt_tuple=fmt_tuple, use_pallas=True,
        interpret=False, nlb_flash=False, plan=plan)
    assert not plan.fallbacks
    weights = {name: tuple(np.shape(v))
               for name, v in module.weight_feeds().items()}
    assert sorted(weights) == sorted(weight_names)
    return core, module, plan, weights


@pytest.mark.parametrize("s,fmt", [(1, None), (1, "5_4"), (4, None),
                                   (4, "5_4")],
                         ids=["fp32", "5_4", "s4-fp32", "s4-5_4"])
def test_braggnn_nest_tier_compiles(one_chip, s, fmt):
    """The whole jitted nest-tier BraggNN(s, img=11) program, as
    ``Design.jax_fn()`` runs it on the chip."""
    core, module, plan, weights = _lower_braggnn(s, fmt)
    for kname in ("conv2d_vmem", "smallfloat_matmul", "fused_softmax"):
        assert any(k.startswith(kname) for k in plan.kernels), plan.kernels
    text = _compile(core, one_chip, (BATCH,) + module.input_shape[1:],
                    weights)
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("fmt", FMTS, ids=["fp32", "5_4"])
def test_conv2d_vmem_unrolled_compiles_at_offline_batch(one_chip, fmt):
    """s=4's conv2a unrolled at the offline cells' batch: its 5184 × 1568
    matrix (32.5 MB) is blocked in N to fit VMEM."""
    def fn(x, w, b):
        return conv2d_vmem(x, w, b, fmt=fmt, fuse_relu=True)
    text = _compile(fn, one_chip, (OFFLINE_BATCH, 64, 9, 9), (32, 64, 3, 3),
                    (32,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s,fmt", [(1, None), (1, "5_4"), (4, None)],
                         ids=["fp32", "5_4", "s4-fp32"])
def test_braggnn_nest_tier_compiles_at_offline_batch(one_chip, s, fmt):
    """The whole program at the offline cells' batch: one ``conv2d_vmem``
    call per conv layer (7), and no im2col patch matrix for the 3×3
    convs, which take the unrolled form."""
    core, module, plan, weights = _lower_braggnn(s, fmt)
    b, c1, c2 = OFFLINE_BATCH, 16 * s, 8 * s
    text = _compile(core, one_chip, (b,) + module.input_shape[1:], weights)
    assert len(re.findall(r"%conv2d_vmem(?:\.\d+)? = \S+ custom-call\(",
                          text)) == 7
    for patches in (f"f32[{b * 81},9]", f"f32[{b * 49},{c1 * 9}]",
                    f"f32[{b},7,7,{c1},9]", f"f32[{b * 25},{c2 * 9}]"):
        assert patches not in text
