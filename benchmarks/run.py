"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Emits ``name,us_per_call,derived`` CSV lines per benchmark plus each
benchmark's own detailed CSV, and aggregates every benchmark's structured
result — including the per-pass ``PassReport`` timings the compiler
records — into a machine-readable ``BENCH_<date>.json`` at the repo root,
so the perf trajectory across PRs is diffable.  Mapping to the paper:
    layers        — Fig. 4   (latency/resources vs unroll, 5 layer types)
    tool_runtime  — Fig. 2/5 (compiler runtime vs trip count)
    braggnn       — §4.2/Fig. 6 (end-to-end case study)
    precision     — Fig. 7   (trained-weight exponents, accuracy sweep)
    roofline      — §Roofline (TPU adaptation; reads dry-run artifacts)
    serving       — deployment: sustained QPS / tail latency / warm boot
    trigger       — hard-real-time trigger: sustained fps / deadline-miss %
                    / drop % / p99 decision latency + part budget check
    compile_scaling — compile-time curve conv2d -> BraggNN -> transformer

Re-running the same day merges into the existing ``BENCH_<date>.json``:
sections whose benchmark was skipped (``--only``) carry forward from the
earlier run instead of being dropped.

When :mod:`repro.obs` is enabled (``REPRO_OBS=1``), the run's metrics
snapshot (cache hits/misses, padding waste, queue-depth histograms, ...)
is embedded under the report's ``"obs"`` key.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

from repro import obs

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

log = obs.get_logger(__name__)


def _timed(name, results, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) * 1e6
    print(f"{name},{dt:.0f},ok")
    sys.stdout.flush()
    results[name] = {"wall_us": round(dt), "result": out}
    return out


def _jsonable(obj):
    """Best-effort conversion of benchmark outputs to JSON values."""
    import numpy as np
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return _jsonable(dataclasses.asdict(obj))
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.floating, np.integer)):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


#: bench_braggnn result fields mirrored into the top-level ``compiler``
#: section: the machine-readable compile-time/throughput trajectory.
_COMPILER_FIELDS = ("build_s", "trace_s", "passes_s", "schedule_s",
                    "pass_ops_per_s", "passes_skipped", "ops_raw", "ops_opt")


def write_report(results: dict, args, out_path=None) -> pathlib.Path:
    """Aggregate all results into ``BENCH_<date>.json`` at the repo root.

    An existing same-day report is MERGED, not clobbered: per-benchmark
    entries and derived sections from benchmarks not re-run this
    invocation (``--only``) are carried forward.
    """
    date = time.strftime("%Y-%m-%d")
    path = pathlib.Path(out_path) if out_path else \
        REPO_ROOT / f"BENCH_{date}.json"
    old = {}
    if path.exists():
        try:
            old = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            old = {}
    # surface per-pass PassReport wall times and compiler throughput as
    # first-class keys so the perf trajectory of the compiler itself is
    # machine-readable across PRs
    pass_times = dict(old.get("pass_times_s") or {})
    compiler = dict(old.get("compiler") or {})
    serving = dict(old.get("serving") or {})
    bragg = results.get("bench_braggnn", {}).get("result") or {}
    if isinstance(bragg, dict) and "pass_s" in bragg:
        pass_times["braggnn"] = bragg["pass_s"]
        compiler["braggnn"] = {k: bragg[k] for k in _COMPILER_FIELDS
                               if k in bragg}
    srv = results.get("bench_serving", {}).get("result") or {}
    if isinstance(srv, dict) and srv:
        # sustained QPS / tail latency / warm-boot trajectory
        serving = _jsonable(srv)
    trig = dict(old.get("trigger") or {})
    tr = results.get("bench_trigger", {}).get("result") or {}
    if isinstance(tr, dict) and tr.get("stream"):
        # sustained fps / deadline-miss % / drop % trajectory
        trig = _jsonable(tr)
    scaling = dict(old.get("compiler_scaling") or {})
    sc = results.get("bench_compile_scaling", {}).get("result") or {}
    if isinstance(sc, dict) and sc.get("workloads"):
        scaling = _jsonable(sc)
    benchmarks = dict(old.get("benchmarks") or {})
    benchmarks.update(_jsonable(results))
    report = {
        "date": date,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "args": {"fast": args.fast, "only": args.only},
        "pass_times_s": pass_times,
        "compiler": compiler,
        "serving": serving,
        "trigger": trig,
        "compiler_scaling": scaling,
        "benchmarks": benchmarks,
    }
    if obs.enabled():
        # metrics collected across the whole run (cache hits, padding
        # waste, queue depths, ...) ride along in the perf trajectory
        report["obs"] = _jsonable(obs.snapshot())
    path.write_text(json.dumps(report, indent=1, sort_keys=True))
    return path


def compare_with_previous(report: dict, path: pathlib.Path) -> None:
    """Print a before/after compile-perf comparison against the most recent
    other ``BENCH_*.json`` in the repo root, when one exists."""
    previous = sorted(p for p in REPO_ROOT.glob("BENCH_*.json")
                      if p.resolve() != path.resolve())
    if not previous:
        return
    prev_path = previous[-1]
    try:
        old = json.loads(prev_path.read_text())
    except (OSError, json.JSONDecodeError):
        return
    old_b = (old.get("benchmarks", {}).get("bench_braggnn", {})
             .get("result") or {})
    new_b = (report["benchmarks"].get("bench_braggnn", {})
             .get("result") or {})
    if not (isinstance(old_b, dict) and isinstance(new_b, dict)
            and old_b.get("build_s") and new_b.get("build_s")):
        return
    speedup = old_b["build_s"] / new_b["build_s"]
    log.info("# compile-perf vs %s: build_s %s -> %s (%.1fx)",
             prev_path.name, old_b["build_s"], new_b["build_s"], speedup)
    old_p, new_p = old_b.get("pass_s") or {}, new_b.get("pass_s") or {}
    for name in sorted(set(old_p) | set(new_p)):
        log.info("#   pass %s: %ss -> %ss", name, old_p.get(name, "-"),
                 new_p.get(name, "-"))
    if new_b.get("pass_ops_per_s"):
        log.info("#   pass-pipeline throughput: %s ops/s%s",
                 f"{new_b['pass_ops_per_s']:,}",
                 (f" (was {old_b['pass_ops_per_s']:,})"
                  if old_b.get("pass_ops_per_s") else ""))


def compare_serving(report: dict, path: pathlib.Path) -> None:
    """Per-metric before/after diff of the ``serving`` section (engine QPS,
    tail latency, warm boot) against the most recent other report."""
    previous = sorted(p for p in REPO_ROOT.glob("BENCH_*.json")
                      if p.resolve() != path.resolve())
    new_s = report.get("serving") or {}
    if not (previous and new_s.get("engine")):
        return
    try:
        old = json.loads(previous[-1].read_text())
    except (OSError, json.JSONDecodeError):
        return
    old_s = old.get("serving") or {}
    nb, ob = new_s["engine"], old_s.get("engine") or {}
    log.info("# serving vs %s:", previous[-1].name)
    for metric in ("qps", "p50_ms", "p95_ms", "p99_ms", "max_queue_depth"):
        log.info("#   engine.%s: %s -> %s", metric, ob.get(metric, "-"),
                 nb.get(metric, "-"))
    for metric in ("cold_compile_s", "warm_boot_s", "warm_speedup"):
        log.info("#   %s: %s -> %s", metric, old_s.get(metric, "-"),
                 new_s.get(metric, "-"))


def compare_trigger(report: dict, path: pathlib.Path) -> None:
    """Before/after diff of the ``trigger`` section (sustained fps,
    deadline-miss %, drop %, p99 decision latency) against the most
    recent other report."""
    previous = sorted(p for p in REPO_ROOT.glob("BENCH_*.json")
                      if p.resolve() != path.resolve())
    new_t = report.get("trigger") or {}
    if not (previous and new_t.get("stream")):
        return
    try:
        old = json.loads(previous[-1].read_text())
    except (OSError, json.JSONDecodeError):
        return
    nb, ob = new_t["stream"], (old.get("trigger") or {}).get("stream") or {}
    log.info("# trigger vs %s:", previous[-1].name)
    for metric in ("sustained_fps", "miss_pct", "drop_pct", "p99_us"):
        log.info("#   stream.%s: %s -> %s", metric, ob.get(metric, "-"),
                 nb.get(metric, "-"))
    check = new_t.get("budget_check") or {}
    if check:
        log.info("#   budget check vs %s: %s", check.get("part", "?"),
                 "PASS" if check.get("passed") else
                 f"FAIL ({', '.join(check.get('failures', []))})")


def compare_compile_scaling(report: dict, path: pathlib.Path) -> None:
    """Per-workload before/after diff of the ``compiler_scaling`` section
    (compile-time curve + scheduler/partition A/Bs) against the most
    recent other report."""
    previous = sorted(p for p in REPO_ROOT.glob("BENCH_*.json")
                      if p.resolve() != path.resolve())
    new_c = report.get("compiler_scaling") or {}
    if not (previous and new_c.get("workloads")):
        return
    try:
        old = json.loads(previous[-1].read_text())
    except (OSError, json.JSONDecodeError):
        return
    old_w = {w["name"]: w
             for w in (old.get("compiler_scaling") or {}).get("workloads",
                                                              [])}
    log.info("# compile scaling vs %s:", previous[-1].name)
    for w in new_c["workloads"]:
        ow = old_w.get(w["name"]) or {}
        log.info("#   %s (%s ops): total_s %s -> %s, ops/s %s -> %s",
                 w["name"], f"{w['ops_raw']:,}", ow.get("total_s", "-"),
                 w["total_s"], ow.get("ops_per_s", "-"), w["ops_per_s"])
    ab = new_c.get("sched_ab") or {}
    if ab:
        log.info("#   scheduler A/B (largest): legacy %ss / python %ss / "
                 "C %ss (%sx vs legacy)", ab.get("legacy_s", "-"),
                 ab.get("python_scalar_s", "-"), ab.get("c_path_s", "-"),
                 ab.get("speedup_vs_legacy", "-"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default=None,
                    help="aggregate JSON path (default: "
                         "BENCH_<date>.json at the repo root)")
    args, _ = ap.parse_known_args()
    obs.setup_logging()

    from benchmarks import (bench_braggnn, bench_compile_scaling,
                            bench_layers, bench_precision, bench_roofline,
                            bench_serving, bench_tool_runtime, bench_trigger)

    todo = args.only.split(",") if args.only else [
        "layers", "tool_runtime", "braggnn", "precision", "roofline",
        "serving", "trigger", "compile_scaling"]

    results: dict = {}
    print("name,us_per_call,derived")
    if "layers" in todo:
        log.info("## Fig4: layer suite ##")
        _timed("bench_layers", results, bench_layers.main)
    if "tool_runtime" in todo:
        log.info("## Fig2/5: tool runtime ##")
        if args.fast:
            bench_tool_runtime.IMAGE_SIZES = (8, 16, 32)
        _timed("bench_tool_runtime", results, bench_tool_runtime.main)
    if "braggnn" in todo:
        log.info("## §4.2: BraggNN case study ##")
        img = 9 if args.fast else 11
        _timed("bench_braggnn", results, bench_braggnn.main, img=img)
    if "precision" in todo:
        log.info("## Fig7: precision study ##")
        steps = 60 if args.fast else 300
        _timed("bench_precision", results, bench_precision.main, steps=steps)
    if "roofline" in todo:
        log.info("## §Roofline: 40-cell table ##")
        _timed("bench_roofline", results, bench_roofline.main)
    if "serving" in todo:
        log.info("## deployment: serving engine under bursty load ##")
        _timed("bench_serving", results, bench_serving.main, fast=args.fast)
    if "trigger" in todo:
        log.info("## deployment: hard-real-time trigger stream ##")
        _timed("bench_trigger", results, bench_trigger.main, fast=args.fast)
    if "compile_scaling" in todo:
        log.info("## compile-time scaling curve ##")
        _timed("bench_compile_scaling", results, bench_compile_scaling.main,
               fast=args.fast)

    path = write_report(results, args, args.out)
    report = json.loads(path.read_text())
    compare_with_previous(report, path)
    compare_serving(report, path)
    compare_trigger(report, path)
    compare_compile_scaling(report, path)
    log.info("# aggregate report: %s", path)


if __name__ == "__main__":
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    main()
