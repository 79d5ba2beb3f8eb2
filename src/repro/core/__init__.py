"""OpenHLS-JAX core: the paper's compiler as a composable JAX module.

Pipeline (paper Fig. 1):
    frontend (loop nests)  ->  symbolic interpretation (interp)  ->
    SSA DFG (ir)  ->  passes (forwarding/relu/fmac/trees/cse/dce)  ->
    resource-constrained list scheduling (schedule)  ->
    emission (emit: functional sim; emit_pallas: compiled rendering)  ->
    behavioural verification (verify)

plus the two TPU-scale adaptations:
    precision — FloPoCo (wE,wF) emulation for weights-in-VMEM deployment
    binding   — the K_i resource-binding rule applied to device meshes
"""

from repro.core import (binding, cachedir, emit, frontend, interp, ir, passes,
                        pipeline, precision, schedule, verify)
from repro.core.binding import BindingRules, DEFAULT_RULES
from repro.core.cachedir import CACHE_FORMAT_VERSION, cache_root
from repro.core.interp import Context, MemRef, SymVal
from repro.core.ir import Graph
from repro.core.passes import optimize
from repro.core.pipeline import (CompiledDesign, CompilerConfig,
                                 CompilerDriver, DesignCache, PassManager,
                                 PassReport, register_pass)
from repro.core.precision import FP_5_3, FP_5_4, FP_5_11, FloatFormat, quantize, ste_quantize
from repro.core.schedule import (Schedule, ScheduleParams, list_schedule,
                                 partition_stages)
from repro.core.verify import run_testbench

__all__ = [
    "binding", "cachedir", "emit", "frontend", "interp", "ir", "passes",
    "pipeline", "precision", "schedule", "verify", "BindingRules",
    "DEFAULT_RULES", "CACHE_FORMAT_VERSION", "cache_root",
    "Context", "MemRef", "SymVal", "Graph", "optimize", "CompiledDesign",
    "CompilerConfig", "CompilerDriver", "DesignCache", "PassManager",
    "PassReport", "register_pass", "FP_5_3", "FP_5_4", "FP_5_11",
    "FloatFormat", "quantize", "ste_quantize", "Schedule", "ScheduleParams",
    "list_schedule", "partition_stages", "run_testbench",
]
