#!/usr/bin/env python3
"""Find a cell's knee: its traffic at a list of fixed rates, on the chip.

    python3 bench/sweep.py --workload <cell> --rates 500,1000 --seconds 5

Each rate runs the cell's own entry for ``--seconds``, in one process with
one compiled design.  The trigger's rate is its frame rate; the engine's
is a steady Poisson rate (base and burst rates both set to it).  For each
rate the sweep prints the frames or requests offered and lost, the
latency percentiles from the due time, the mean latency of the first and
last quarter of the window (a backlog that grows shows as a rising mean)
and how late the load generator ran.  The knee is the highest rate with
nothing lost and no rising latency; a cell's traffic file records a rate
below it.  Not part of a benchmark run.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import run as bench_run, spec  # noqa: E402

RATE_KEYS = {"trigger": ("frame_rate_hz",),
             "engine": ("base_rate", "burst_rate")}


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)

    bench = spec.load_benchmark()
    cell = spec.cell(bench, a.workload)
    cfg = spec.config(bench, cell)
    traffic = spec.traffic(cell)
    model = spec.model(cfg["model"])
    driver = spec.driver(traffic["entry"])
    bench_run.device_info(cell["chips"])
    import jax
    import repro.hls as hls
    bench_run.enable_caches()
    params = jax.block_until_ready(model.make_params(cfg, a.seed))
    design = hls.compile(model.build_module(cfg, params),
                         cache=bench_run.DESIGN_CACHE)
    args = bench_run.parse(["--workload", a.workload, "--seed", str(a.seed),
                            "--seconds", str(a.seconds)])
    for rate in (float(r) for r in a.rates.split(",")):
        tr = dict(traffic, **{k: rate for k in RATE_KEYS[traffic["entry"]]})
        ctx = bench_run.Context(args, cfg, tr, model, trace=False)
        res = driver.run(ctx, design)
        rec = res["records"]
        lat = np.asarray(rec.get("decision_latency_s",
                                 np.asarray(rec.get("latency_ms", [])) * 1e-3))
        q = max(len(lat) // 4, 1)
        late = np.asarray(rec.get("gen_late_s", [0.0]))
        row = {"rate": rate, "attempted": res["attempted"],
               "failed": res["failed"],
               "p50_ms": float(np.percentile(lat, 50)) * 1e3,
               "p99_ms": float(np.percentile(lat, 99)) * 1e3,
               "first_quarter_ms": float(lat[:q].mean()) * 1e3,
               "last_quarter_ms": float(lat[-q:].mean()) * 1e3,
               "gen_late_p99_ms": float(np.percentile(late, 99)) * 1e3,
               "gen_late_max_ms": float(late.max()) * 1e3,
               "gen_late_max_by_tenth_ms": [
                   round(float(part.max()) * 1e3, 3)
                   for part in np.array_split(late, 10) if len(part)],
               "calls": rec.get("calls")}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
