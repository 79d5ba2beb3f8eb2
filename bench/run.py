#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights from the seed, compiles the design with
``hls.compile``, renders the cell's inputs and warms every shape the cell's
traffic uses; ``setup_s`` runs from the start of this script to the start of
the window.  The traffic then runs for ``--seconds`` (a traced run traces a
window of at most ``TRACE_SECONDS``).  Once the window has closed and the
program's state is freed, the plain reference runs over every input whose
answer the window produced, and the result decides ``correct``.

The last line of standard output is one JSON object: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``.  The
last lines of standard error give each number compared beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, devtrace, spec  # noqa: E402

#: longest traced window: a trace of a longer one is large and slow to read
TRACE_SECONDS = 3.0
#: where a traced run writes its profile, removed once it is read
TRACE_DIR = ROOT / ".bench_trace"
#: the design cache of ``hls.compile``, at a fixed path in the checkout
DESIGN_CACHE = ROOT / ".repro_cache" / "bench"
#: JAX's persistent compilation cache, at a fixed path in the checkout: the
#: path is part of every entry's key, and one set in the environment could
#: be shared with another checkout
JAX_CACHE = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(chips: int) -> dict:
    """The devices JAX finds; exits non-zero unless they are enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX runs on {devs[0].platform!r})")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX finds "
                 f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_caches() -> None:
    """Turn on JAX's persistent compilation cache, in the checkout."""
    import jax
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    # no size limit, so no eviction: its bookkeeping fails on an entry
    # written without it, and the cell's programs take tens of MB
    jax.config.update("jax_compilation_cache_max_size", -1)


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Context:
    """What a driver gets: the cell's pieces, and the window's two marks."""

    def __init__(self, args, cfg, traffic, model, trace: bool):
        self.seed = args.seed
        self.seconds = (min(args.seconds, TRACE_SECONDS) if trace
                        else float(args.seconds))
        self.cfg, self.traffic, self.model = cfg, traffic, model
        self.trace = trace
        fmt = cfg["fmt"]
        self.fmt_key = f"{fmt[0]}_{fmt[1]}" if fmt else None
        self.backend = cfg["backend"]
        self.setup_parts: dict[str, float] = {}
        self.setup_s = None
        self.window_mono = (0.0, 0.0)
        self._trace_span = None

    def part(self, name: str, t0: float) -> float:
        """Record one part of set-up that began at ``t0``."""
        dt = time.perf_counter() - t0
        self.setup_parts[name] = dt
        log(f"set-up: {name} {dt:.3f} s")
        return dt

    def window_start(self) -> float:
        # a latency-bound Python service freezes the heap its start-up
        # leaves, so that full collections do not walk the compiler's and
        # JAX's objects in the window
        gc.collect()
        gc.freeze()
        now = time.perf_counter()
        self.setup_s = now - T_START
        log(f"set-up: total {self.setup_s:.3f} s; window of "
            f"{self.seconds:g} s starts")
        self.window_mono = (time.monotonic(), 0.0)
        if self.trace:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=devtrace.options())
            self._trace_span = jax.profiler.TraceAnnotation("bench.window")
            self._trace_span.__enter__()
        return time.perf_counter()

    def window_end(self) -> None:
        """Close the window; a second call changes nothing."""
        if self.window_mono[1]:
            return
        self.window_mono = (self.window_mono[0], time.monotonic())
        if self._trace_span is not None:
            import jax
            self._trace_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._trace_span = None


def run(args, *, bench=None, require_tpu: bool = True) -> dict:
    bench = bench or spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell)
    traffic = spec.traffic(cell)
    model = spec.model(cfg["model"])
    driver = spec.driver(traffic["entry"])
    trace = bool(args.trace)

    t0 = time.perf_counter()
    if require_tpu:
        device = device_info(cell["chips"])
    else:
        import jax
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    import jax
    import repro.hls as hls
    enable_caches()
    ctx = Context(args, cfg, traffic, model, trace)
    ctx.part("jax_start", t0)

    t0 = time.perf_counter()
    params = jax.block_until_ready(model.make_params(cfg, args.seed))
    ctx.part("weights", t0)
    t0 = time.perf_counter()
    design = hls.compile(model.build_module(cfg, params),
                         name=f"bench_{cell['config']}", cache=DESIGN_CACHE)
    ctx.part("hls_compile", t0)
    if trace:
        devtrace.annotate_calls(design)
        if traffic.get("obs"):
            from repro import obs
            obs.enable()

    result = driver.run(ctx, design)
    mem = memory_peak_bytes(cell["chips"])
    records = result.pop("records", {})
    if trace and traffic.get("obs"):
        from repro import obs
        records["obs_spans"] = devtrace.obs_spans(obs.tracer,
                                                  *ctx.window_mono)
        obs.disable()
    del design, result["state"]
    gc.collect()

    # the reference, once the program's state is freed
    t0 = time.perf_counter()
    ref_rows = model.reference(params, result["inputs"], cfg)
    checks = check.judge(cfg, ref_rows, result)
    log(f"reference: {time.perf_counter() - t0:.3f} s over "
        f"{len(result['inputs'])} inputs, {len(result['outputs'])} answers")
    correct = check.passed(checks)

    out = {"correct": correct, "attempted": int(result["attempted"]),
           "failed": int(result["failed"]), "metrics": {}, "device": device}
    out["device"]["memory_peak_bytes"] = mem
    if not trace:
        values = dict(result["e2e"], setup_s=ctx.setup_s)
        for m in spec.e2e_metrics(bench, cell["name"]):
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    else:
        tr = devtrace.load(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        view = devtrace.RunView(cell=cell, cfg=cfg, traffic=traffic,
                                model=model, trace=tr, records=records,
                                setup_parts=ctx.setup_parts,
                                peaks=spec.peaks(device["kind"])
                                if require_tpu else None)
        for m in spec.per_layer_metrics(bench, cell["name"]):
            value = spec.reader(m["name"]).read(view)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        out["device"]["busy_s"] = tr.busy_s()
        out["device"]["window_s"] = tr.window_s()
        out["breakdown"] = tr.breakdown()
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    out = run(parse(argv))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
