"""``repro.hls`` — the one public compile-to-serve API.

High-level representations of DNNs in, deployable low-level designs out
(the paper's pitch, hls4ml's ``convert(model) -> hls_model`` shape)::

    import repro.hls as hls
    from repro.models import braggnn

    model = braggnn.build(s=1, params=trained_params)   # described once
    design = hls.compile(model, config=hls.CompilerConfig(n_stages=3))
    report = design.serve(batches, fmt="5_4")           # warmed, batched

``compile`` accepts a jax-level :class:`~repro.nn.graph.ModuleGraph`
(auto-lowered to the paper's loop nests by :mod:`repro.hls.bridge` —
bit-identical to the hand-written programs), a loop-nest build callable,
or a traced ``Graph``.  The returned :class:`Design` carries the verbs:
``run`` (vectorised evaluate), ``jax_fn`` (the compiled rendering),
``verify`` (behavioural testbench), ``tune`` / ``apply_tuned``
(``repro.tune`` search, persisted + auto-loaded via the ``TuningDB``),
``with_config`` (recompile sharing the trace), ``serve`` (warmed batched
loop) and ``report``.

Deployment round-trips through warm-boot artifacts:
``design.save(path)`` persists the compiled design + bound weights +
warmed-bucket manifest, ``hls.load(path)`` boots it back without
re-compiling, and ``design.engine()`` fronts it with the async
adaptive-batching engine (``repro.serving.design_engine``).

``repro.core`` stays importable as the stable internal layer; this
package adds no compiler logic, only the front door.
"""

from repro.core.pipeline import CompiledDesign, CompilerConfig
from repro.hls.api import (Design, ServeReport, Session, compile, load,
                           trace, _default_session)
from repro.nn.graph import ModuleGraph

__all__ = [
    "compile",
    "load",
    "trace",
    "Design",
    "Session",
    "ServeReport",
    "CompilerConfig",
    "CompiledDesign",
    "ModuleGraph",
]
