#!/usr/bin/env python3
"""Read the numbers that decide ``correct``, for the program and the control.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: the cell's own entry runs its window, its
answers are compared with the reference (the program's reading), and the
configuration's control, the reference one precision step lower, is
compared with the reference over the same inputs (the control's reading).
A limit lies between the largest reading of the program and the smallest
of the control.  Prints one JSON line per seed.  Not part of a benchmark
run.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, run as bench_run, spec  # noqa: E402


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
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
    for seed in (int(s) for s in a.seeds.split(",")):
        params = jax.block_until_ready(model.make_params(cfg, seed))
        design = hls.compile(model.build_module(cfg, params),
                             cache=bench_run.DESIGN_CACHE)
        args = bench_run.parse(["--workload", a.workload, "--seed",
                                str(seed), "--seconds", str(a.seconds)])
        ctx = bench_run.Context(args, cfg, traffic, model, trace=False)
        res = driver.run(ctx, design)
        del design, res["state"]
        ref = model.reference(params, res["inputs"], cfg)
        prog = check.judge(cfg, ref, res)
        ctl = model.reference(params, res["inputs"], cfg, control=True)
        worst, mean = check.rel_errs(ctl[res["input_index"]],
                                     ref[res["input_index"]])
        print(json.dumps({
            "seed": seed, "answers": len(res["outputs"]),
            "program": {k: v["value"] for k, v in prog.items()},
            "control": {"out_rel_err": worst, "out_mean_rel_err": mean},
            "e2e": res["e2e"]}), flush=True)


if __name__ == "__main__":
    main()
