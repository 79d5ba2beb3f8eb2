"""Binding rules + sharding resolution (pure logic, no devices needed) and
a subprocess dry-run on a small placeholder mesh."""

import json
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.core.binding import BindingRules


class _FakeMesh:
    """Duck-typed mesh: BindingRules only reads ``.shape``."""

    def __init__(self, shape: dict):
        self.shape = shape


MESH = _FakeMesh({"data": 16, "model": 16})
MESH3 = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_default_rules_bind_ki_axes():
    r = BindingRules()
    assert r.spec(("batch", None), MESH3) == P(("pod", "data"), None)
    assert r.spec(("embed", "mlp"), MESH) == P(None, "model")
    assert r.spec(("experts", "embed", "expert_mlp"), MESH) == \
        P("model", None, None)
    assert r.spec(("vocab", "embed"), MESH) == P("model", None)


def test_duplicate_mesh_axes_deduped():
    r = BindingRules().with_overrides(embed="model")
    # both dims want 'model': only the first gets it
    assert r.spec(("embed", "mlp"), MESH) == P("model", None)


def test_K_replication_factor():
    r = BindingRules()
    assert r.K(("batch",), MESH3) == 32
    assert r.K(("heads", None), MESH) == 16
    assert r.K((None, None), MESH) == 1


def test_overrides_shadow_defaults():
    r = BindingRules().with_overrides(heads=None, head_dim="model")
    assert r.spec(("embed", "heads", "head_dim"), MESH) == \
        P(None, None, "model")


def test_prune_spec_divisibility():
    from repro.launch.shardings import prune_spec
    import jax
    if jax.device_count() < 1:
        pytest.skip("needs a device")
    from repro.launch.mesh import single_device_mesh
    mesh = single_device_mesh()
    # sizes divide trivially on a 1x1 mesh
    assert prune_spec((4, 4), P("data", "model"), mesh) == P("data", "model")


def test_prune_drops_nondividing_axes():
    from repro.launch.shardings import prune_spec

    class M:
        shape = {"data": 16, "model": 16}

    # batch=1 can't shard 16 ways -> dropped; 60 not divisible -> dropped
    assert prune_spec((1, 128), P("data", None), M) == P(None, None)
    assert prune_spec((60, 64), P("model", None), M) == P(None, None)
    assert prune_spec((64, 64), P("model", None), M) == P("model", None)
    # multi-axis entries pruned partially: ('pod','data') on 32 -> kept,
    # on 2 -> only pod kept
    class M3:
        shape = {"pod": 2, "data": 16, "model": 16}
    assert prune_spec((32,), P(("pod", "data")), M3) == P(("pod", "data"))
    assert prune_spec((2,), P(("pod", "data")), M3) == P("pod")


DRYRUN_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import AxisType
from repro.configs import registry
from repro.launch import dryrun as dr

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
cfg = registry.get_tiny("gemma2-27b").replace(microbatches=2)
with jax.set_mesh(mesh):   # build_cell traces eval_shape -> needs a context
    step, args, in_sh, out_sh, donate = dr.build_cell(
        "gemma2-27b", "train_4k", mesh, cfg=cfg)
# shrink the workload to the tiny config scale
import jax.numpy as jnp
inputs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
          "targets": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
from repro.launch import shardings as sh
rules = sh.rules_for(cfg)
input_sh = {k: sh.sharding_for(tuple(v.shape), ("batch", None), mesh, rules)
            for k, v in inputs.items()}
args = (args[0], args[1], inputs)
in_sh = (in_sh[0], in_sh[1], input_sh)
from repro.launch.steps import make_train_step
micro_sh = {k: sh.sharding_for((2, 4) + tuple(v.shape[1:]),
                               (None, "batch", None), mesh, rules)
            for k, v in inputs.items()}
step = make_train_step(cfg, microbatch_shardings=micro_sh)
import jax
with jax.set_mesh(mesh):   # P-based activation constraints need a context
    out_abs = jax.eval_shape(step, *args)
    metrics_sh = jax.tree_util.tree_map(lambda _: sh.replicated(mesh),
                                        out_abs[2])
    compiled = jax.jit(step, in_shardings=(in_sh[0], in_sh[1], input_sh),
                       out_shardings=(in_sh[0], in_sh[1], metrics_sh),
                       donate_argnums=(0, 1)).lower(*args).compile()
print("COMPILED", compiled.memory_analysis().temp_size_in_bytes)
"""


def test_small_mesh_dryrun_subprocess():
    """Lower+compile a tiny heterogeneous (local/global, post-norm) arch on
    a 2x2x2 placeholder mesh in a fresh process (8 fake devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", DRYRUN_SNIPPET], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "COMPILED" in out.stdout
