"""Candidate evaluation: compile (cached), validate numerics, cost latency.

Every candidate accepted by the tuner passes through three gates here:

  1. **compile** — through ``CompilerDriver`` and its design cache, so a
     re-proposed candidate (or a rerun of the whole search) is free; the
     driver's pass-stage memo additionally lets candidates that differ only
     in schedule knobs share one pass-pipeline run.
  2. **numerics** — the candidate's optimised graph is functionally
     simulated (at the candidate's FloPoCo format, if any) and compared
     against the *interpreter reference*: the raw traced DFG evaluated in
     fp32, i.e. the symbolic-interpretation semantics of ``core.interp``.
     Candidates outside tolerance are marked invalid and can never win.
  3. **latency** — the objective.  The primary metric is the scheduled
     design's per-sample latency (initiation interval x 10 ns for
     stage-pipelined designs, else makespan x 10 ns — the paper's interval
     counts).  In ``measure`` mode the design's compiled rendering (the
     DFG tier of ``emit_pallas``) is additionally wall-clocked; in
     ``--dry`` mode a roofline-style cost model (``launch.roofline``
     machine constants) estimates the CPU path instead.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Union

import numpy as np

from repro.core import emit, verify
from repro.core.interp import Context
from repro.core.ir import Graph
from repro.core.ir import OPCODES as ir_OPCODES
from repro.core.pipeline import CompiledDesign, CompilerDriver
from repro.tune.space import Candidate, SearchSpace

#: FLOPs per opcode (fmac counts two) for the roofline estimate, as a dense
#: per-opcode-id lookup aligned with ``ir.OPCODES``.  (The historical table
#: keyed on resource-class-style names — "add", "mul" — which never matched
#: the actual "addf"/"mulf" opcodes, so plain adds and muls were costed 0.)
_FLOPS_BY_NAME = {"addf": 1, "subf": 1, "mulf": 1, "divf": 1, "sqrtf": 1,
                  "fmac": 2, "maxf": 1, "minf": 1, "cmpugt": 1, "negf": 1,
                  "relu": 1, "select": 1}
_FLOPS_TABLE = np.array([_FLOPS_BY_NAME.get(name, 0) for name in ir_OPCODES],
                        dtype=np.int64)


@dataclasses.dataclass
class Trial:
    """The full record of one evaluated candidate."""

    candidate: Candidate
    design_hash: str
    latency_us: float             # objective: scheduled per-sample latency
    makespan: int
    stage_ii: Optional[int]
    err: float                    # vs the interpreter reference
    valid: bool                   # within tolerance -> eligible to win
    resources: dict[str, int]
    wire_bits: int                # per-value wire width at this precision
    #: Roofline-model estimate of the emitted tensor path on the repo's
    #: reference accelerator (v5e constants from ``launch.roofline``) —
    #: NOT a CPU prediction; compare roofline-to-roofline only.
    est_roofline_us: float
    measured_cpu_us: Optional[float]  # wall-clocked (measure mode only)
    compile_s: float
    cached: bool                  # design served from the design cache
    #: trigger-budget gate verdict (True when no budget was configured);
    #: an infeasible candidate scores ``None`` and can never win
    feasible: bool = True
    #: the named constraints the candidate blew (``DSP``, ``latency_us``...)
    budget_failures: list = dataclasses.field(default_factory=list)

    def score(self) -> Optional[tuple]:
        """Ordering key: lower is better; ``None`` = ineligible.

        Latency first, then DSP units, then wire bits (the SLL-crossing
        pressure that forced the paper's (5,4) -> (5,3) step).  Both
        gates bite here: numerics-invalid and budget-infeasible trials
        are ineligible.
        """
        if not self.valid or not self.feasible:
            return None
        return (self.latency_us, self.resources.get("DSP", 0),
                self.wire_bits)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["candidate"] = self.candidate.to_json()
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Trial":
        # tolerate schema drift (the DB's version gate discards truly
        # incompatible files; this guards same-version additive changes)
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        d["candidate"] = Candidate.from_json(d["candidate"])
        return cls(**d)

    def summary(self) -> str:
        tag = ("ok" if self.valid and self.feasible
               else "INVALID" if not self.valid
               else f"OVER BUDGET ({', '.join(self.budget_failures)})")
        cpu = (f", cpu={self.measured_cpu_us:.1f}us"
               if self.measured_cpu_us is not None else "")
        return (f"[{tag}] {self.latency_us:8.2f} us  "
                f"(makespan={self.makespan}, ii={self.stage_ii}, "
                f"err={self.err:.2e}, dsp={self.resources.get('DSP', 0)}"
                f"{cpu})  {self.candidate.label()}")


def roofline_estimate_us(design: CompiledDesign) -> float:
    """Roofline cost model of the emitted tensor path (``--dry`` fallback).

    max(compute term, memory term) over the optimised DFG, using the
    ``launch.roofline`` machine constants (the repo's v5e reference
    accelerator — so this estimates the deployed-accelerator path, not the
    local CPU): each arithmetic op is one FLOP (fmac: two) and every SSA
    value crosses memory once at 4 bytes.
    """
    from repro.launch.roofline import HBM_BW, PEAK_FLOPS
    g = design.graph_opt
    flops = int(_FLOPS_TABLE[g.cols().opcode].sum())
    bytes_moved = 4.0 * g.n_values
    return max(flops / PEAK_FLOPS, bytes_moved / HBM_BW) * 1e6


class Evaluator:
    """Compile + validate + cost one candidate at a time.

    ``program`` is either a build callable (traced once, here) or an
    already-traced ``Graph`` — the trace is *shared* across all candidates,
    so per-candidate cost is passes + schedule only (and just schedule when
    the pass-stage memo hits).

    tolerances:
        ``tol_abs`` gates fp32 candidates (reassociation-level error);
        ``tol_rel`` gates quantised candidates on max relative error
        against the fp32 interpreter reference.

    ``budget`` (a :class:`repro.trigger.TriggerBudget`) adds the trigger
    feasibility gate: every candidate's compiled schedule is checked
    against the envelope and an over-budget trial is marked infeasible —
    ineligible to win, exactly like a numerics-invalid one.
    """

    def __init__(self, program: Union[Graph, "BuildFn"], space: SearchSpace,
                 *, driver: Optional[CompilerDriver] = None,
                 name: str = "design", batch: int = 2, seed: int = 0,
                 scale: float = 0.4, tol_abs: float = 1e-3,
                 tol_rel: float = 5e-2, measure: bool = False,
                 measure_reps: int = 5, budget=None):
        self.driver = driver or CompilerDriver()
        self.space = space
        self.name = name
        self.tol_abs = tol_abs
        self.tol_rel = tol_rel
        self.measure = measure
        self.measure_reps = measure_reps
        self.budget = budget
        self.batch = batch
        self.seed = seed
        self.scale = scale
        if isinstance(program, Graph):
            self.graph = program
        else:
            ctx = Context(forward=space.base.forward)
            program(ctx)
            self.graph = ctx.finalize()
        self.feeds = verify.random_feeds(self.graph, batch=batch, seed=seed,
                                         scale=scale)
        # the interpreter reference: raw traced DFG, fp32 — computed once
        self.ref = emit.evaluate(self.graph, self.feeds)
        self._ref_denom = max(
            (float(np.abs(v).max()) for v in self.ref.values()),
            default=0.0) + 1e-9
        # numerics depend only on (optimised graph, format): memoise
        self._err_memo: dict[tuple[str, str], float] = {}
        self._cpu_memo: dict[str, float] = {}
        self.n_evals = 0

    def settings(self) -> dict:
        """Everything that shapes a trial besides the candidate itself.

        Stored with each ``TuningDB`` entry: a rerun is only served from
        the DB when its evaluation settings match — a different feed
        scale, tolerance, or measure mode is a different experiment.
        """
        return {"batch": self.batch, "seed": self.seed, "scale": self.scale,
                "tol_abs": self.tol_abs, "tol_rel": self.tol_rel,
                "mode": "measure" if self.measure else "dry",
                "budget": self.budget.key() if self.budget is not None
                else None}

    # -- gates --------------------------------------------------------------

    def _numeric_err(self, design: CompiledDesign, fmt) -> float:
        key = (design.config.pass_key(), str(fmt) if fmt else "fp32")
        err = self._err_memo.get(key)
        if err is None:
            out = emit.evaluate(design.graph_opt, self.feeds, fmt=fmt)
            err = max(float(np.abs(out[k] - self.ref[k]).max())
                      for k in self.ref)
            self._err_memo[key] = err
        return err

    def _measure_cpu_us(self, design: CompiledDesign) -> float:
        """Wall-clock the compiled rendering's DFG tier (us per sample).

        Memoised on the pass key — the lowered function depends only on the
        optimised graph, never on the schedule knobs.
        """
        key = design.config.pass_key()
        cached = self._cpu_memo.get(key)
        if cached is not None:
            return cached
        import jax
        fn = design.jax_fn()                         # jitted already
        batch = len(next(iter(self.feeds.values())))
        jax.block_until_ready(fn(self.feeds))        # compile + warm up
        t0 = time.perf_counter()
        for _ in range(self.measure_reps):
            out = fn(self.feeds)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / (self.measure_reps * batch) * 1e6
        self._cpu_memo[key] = us
        return us

    # -- the evaluation -----------------------------------------------------

    def evaluate(self, candidate: Candidate) -> Trial:
        cfg = self.space.to_config(candidate)
        fmt = self.space.to_format(candidate)

        misses = self.driver.cache.misses
        t0 = time.perf_counter()
        design = self.driver.compile(self.graph, name=self.name, config=cfg)
        compile_s = time.perf_counter() - t0
        cached = self.driver.cache.misses == misses

        err = self._numeric_err(design, fmt)
        tol = self.tol_abs if fmt is None else self.tol_rel * self._ref_denom
        valid = err <= tol

        feasible, failures = True, []
        if self.budget is not None:
            from repro.trigger.budget import check_design
            rep = check_design(design, self.budget)
            feasible, failures = rep.passed, rep.failures

        measured = self._measure_cpu_us(design) if self.measure else None
        self.n_evals += 1
        return Trial(
            candidate=candidate, design_hash=design.design_hash,
            latency_us=design.sample_latency_us, makespan=design.makespan,
            stage_ii=design.stage_ii, err=err, valid=valid,
            resources=design.schedule.resources(),
            wire_bits=fmt.wire_bits if fmt is not None else 32,
            est_roofline_us=roofline_estimate_us(design),
            measured_cpu_us=measured, compile_s=compile_s, cached=cached,
            feasible=feasible, budget_failures=failures)

    def compile_candidate(self, candidate: Candidate) -> CompiledDesign:
        """The design for a (stored) candidate — how serving loads a win."""
        return self.driver.compile(self.graph, name=self.name,
                                   config=self.space.to_config(candidate))
