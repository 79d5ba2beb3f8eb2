"""Serving benchmark: sustained QPS + tail latency under bursty open load.

    PYTHONPATH=src python -m benchmarks.bench_serving [--fast]
        [--assert-healthy]

The paper's headline is µs/sample in a warm loop; a deployed detector
pipeline instead sees an *open-loop* arrival process — requests arrive on
the experiment's clock whether or not the replica keeps up.  This bench
drives :class:`repro.serving.design_engine.DesignEngine` over a compiled
BraggNN(s=1) with a seeded bursty schedule (Poisson base rate with
periodic burst windows) and reports:

  * sustained QPS (completed / span of completions),
  * p50/p95/p99 per-request latency (queueing + batching + compute),
  * max/mean queue depth, dispatch bucket histogram, padded samples.

It also measures the warm-boot claim in the same run: cold boot = full
``hls.compile`` in a fresh Session + engine bucket warm-up, warm boot =
``hls.load`` of the ``Design.save`` artifact + the same warm-up.  The
aggregate lands in ``BENCH_<date>.json`` under ``"serving"`` via
``benchmarks/run.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import pathlib
import sys
import tempfile
import time

import jax
import numpy as np

import repro.hls as hls
from repro import obs
from repro.models import braggnn

log = obs.get_logger(__name__)


@dataclasses.dataclass
class BurstyLoad:
    """Open-loop arrival schedule: Poisson base rate + burst windows.

    Deterministic given ``seed`` — every run (and every PR) sees the
    same arrival times.  Requests ``burst_len``-out-of-``burst_every`` are
    drawn at ``burst_qps``; arrivals never wait for completions.
    """

    n_requests: int = 240
    base_qps: float = 400.0
    burst_qps: float = 1500.0
    burst_every: int = 60
    burst_len: int = 20
    seed: int = 0

    def schedule(self) -> list[float]:
        """Arrival offsets (s, from load start), strictly increasing."""
        rng = np.random.default_rng(self.seed)
        t, out = 0.0, []
        for i in range(self.n_requests):
            rate = (self.burst_qps if (i % self.burst_every) < self.burst_len
                    else self.base_qps)
            t += float(rng.exponential(1.0 / rate))
            out.append(t)
        return out

    def drive(self, engine, samples: list[np.ndarray]) -> list:
        """Submit ``samples`` (cycled) at the scheduled times; returns the
        request futures.  Open loop: a late engine only grows the queue."""
        sched = self.schedule()
        t0 = time.perf_counter()
        reqs = []
        for i, at in enumerate(sched):
            delay = at - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            reqs.append(engine.submit(samples[i % len(samples)]))
        return reqs

    def describe(self) -> dict:
        return {"n_requests": self.n_requests, "base_qps": self.base_qps,
                "burst_qps": self.burst_qps, "burst_every": self.burst_every,
                "burst_len": self.burst_len, "seed": self.seed}


def _samples(img: int, n: int = 32, seed: int = 1) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.normal(0.0, 0.25, (1, 1, img, img)).astype(np.float32)
            for _ in range(n)]


def _bench_engine(design, load: BurstyLoad, img: int,
                  max_batch: int) -> dict:
    eng = design.engine(fmt=None, max_batch=max_batch, max_delay_ms=2.0)
    with eng:
        reqs = load.drive(eng, _samples(img))
        for r in reqs:
            r.wait(timeout=300)
    rep = eng.report()
    return {
        "qps": round(rep.qps, 1),
        "p50_ms": round(rep.p50_ms, 3),
        "p95_ms": round(rep.p95_ms, 3),
        "p99_ms": round(rep.p99_ms, 3),
        "mean_ms": round(rep.mean_ms, 3),
        "completed": rep.completed,
        "dropped": rep.dropped,
        "dispatches": rep.dispatches,
        "batch_hist": {str(k): v for k, v in sorted(rep.batch_hist.items())},
        "padded_samples": rep.padded_samples,
        "max_queue_depth": rep.max_queue_depth,
        "mean_queue_depth": rep.mean_queue_depth,
        "boot_s": round(rep.boot_s, 3),
        "served": rep.served,
    }


def main(fast: bool = False) -> dict:
    img = 9 if fast else 11
    max_batch = 8 if fast else 16
    load = BurstyLoad(n_requests=60 if fast else 240)

    model = braggnn.build(1, img)
    params = model.init_params(jax.random.key(0))
    bound = model.bind(params)

    # cold boot: trace + passes + schedule in a fresh Session, then the
    # engine's bucket warm-up — everything a brand-new replica pays
    t0 = time.perf_counter()
    design = hls.Session().compile(bound, name="braggnn_serve")
    design.engine(max_batch=max_batch)
    cold_s = time.perf_counter() - t0

    out: dict = {"model": f"braggnn_s1_img{img}", "max_batch": max_batch,
                 "load": load.describe()}

    with tempfile.TemporaryDirectory() as td:
        artifact = pathlib.Path(td) / "braggnn_s1.design"
        design.save(artifact)
        out["artifact_bytes"] = artifact.stat().st_size

        # warm boot: one disk read + the SAME bucket warm-up, no compile
        t0 = time.perf_counter()
        warmed = hls.load(artifact)
        warmed.engine(max_batch=max_batch)
        warm_s = time.perf_counter() - t0
        out["cold_compile_s"] = round(cold_s, 3)
        out["warm_boot_s"] = round(warm_s, 3)
        out["warm_speedup"] = round(cold_s / warm_s, 1)
        print(f"serving_cold_boot,{cold_s * 1e6:.0f},compile+warm")
        print(f"serving_warm_boot,{warm_s * 1e6:.0f},"
              f"{out['warm_speedup']}x_faster")

        res = out["engine"] = _bench_engine(warmed, load, img, max_batch)
        print(f"serving_engine,{res['p95_ms'] * 1e3:.0f},{res['qps']}qps")
        sys.stdout.flush()
    return out


def check_healthy(result: dict) -> list[str]:
    """Sanity assertions for CI: the engine completed everything."""
    problems = []
    if result["warm_boot_s"] >= result["cold_compile_s"]:
        problems.append(
            f"warm boot ({result['warm_boot_s']}s) not faster than cold "
            f"compile ({result['cold_compile_s']}s)")
    b = result["engine"]
    if b["qps"] <= 0:
        problems.append(f"qps {b['qps']} <= 0")
    if b["dropped"]:
        problems.append(f"dropped {b['dropped']} requests")
    if b["completed"] != result["load"]["n_requests"]:
        problems.append(f"completed {b['completed']} != "
                        f"submitted {result['load']['n_requests']}")
    return problems


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out", default=None, help="write result JSON here")
    ap.add_argument("--assert-healthy", action="store_true",
                    help="exit 1 unless QPS>0 and zero dropped everywhere")
    a = ap.parse_args()
    obs.setup_logging()
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    result = main(fast=a.fast)
    b = result["engine"]
    log.info("# engine: %s qps, p50 %sms / p95 %sms / p99 %sms, "
             "max queue %s, %s dispatches %s", b["qps"],
             b["p50_ms"], b["p95_ms"], b["p99_ms"],
             b["max_queue_depth"], b["dispatches"], b["batch_hist"])
    log.info("# boot: cold %ss vs warm %ss (%sx)",
             result["cold_compile_s"], result["warm_boot_s"],
             result["warm_speedup"])
    if a.out:
        import json
        pathlib.Path(a.out).write_text(json.dumps(result, indent=1))
    if a.assert_healthy:
        issues = check_healthy(result)
        for p in issues:
            log.error("# UNHEALTHY: %s", p)
        sys.exit(1 if issues else 0)
