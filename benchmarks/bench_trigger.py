"""Hard-real-time trigger: sustained frame rate, deadlines, drops.

The deployment figure OpenHLS is actually judged on: BraggNN serving a
fixed-rate detector stream as a trigger.  This benchmark runs a seeded :class:`~repro.trigger.DetectorFeed` (event rate
+ pileup bursts) through a pre-warmed :class:`~repro.trigger.TriggerLoop`
in realtime mode and reports

  * sustained frame rate vs the configured one,
  * deadline-miss % against a per-decision latency budget,
  * drop % out of the drop-oldest ring,
  * p50/p95/p99 decision latency (arrival -> accept/reject),

plus the :meth:`Design.check_budget` verdict against the paper's
deployment part (``alveo_u280``) — the schedule-level contract next to
the measured stream-level numbers.  Feeds the ``trigger`` section of
``BENCH_<date>.json`` via ``benchmarks/run.py``.
"""

from __future__ import annotations

import time

import jax

import repro.hls as hls
from repro import obs, trigger
from repro.models import braggnn

log = obs.get_logger(__name__)

#: per-decision deadline (µs) for the realtime run — generous enough that
#: a warm CPU-simulated design holds it, tight enough that a regression
#: (or an unwarmed shape on the hot path) shows up as misses
DEADLINE_US = 50_000.0


def run_stream(design, *, img: int, n_frames: int, rate_hz: float,
               window: int) -> dict:
    budget = trigger.TriggerBudget(max_latency_us=DEADLINE_US)
    t0 = time.perf_counter()
    loop = design.trigger(window=window, budget=budget)
    loop.calibrate(trigger.DetectorFeed(img=img, seed=11), 64)
    build_s = time.perf_counter() - t0
    feed = trigger.DetectorFeed(img=img, frame_rate_hz=rate_hz, seed=11)
    rep = loop.run(feed, n_frames, realtime=True)
    log.info("  %s", rep.summary())
    out = rep.to_json()
    out.update(build_s=round(build_s, 2), threshold=loop.threshold,
               configured_fps=rate_hz,
               rate_sustained=rep.sustained_fps >= 0.95 * rate_hz)
    for k in ("p50_us", "p95_us", "p99_us", "max_us", "sustained_fps",
              "wall_s", "warmup_s"):
        out[k] = round(out[k], 1)
    return out


def main(fast: bool = False) -> dict:
    img = 9 if fast else 11
    n_frames = 200 if fast else 1000
    rate_hz = 500.0 if fast else 1000.0
    window = 4

    model = braggnn.build(1, img)
    params = model.init_params(jax.random.key(0))
    design = hls.Session().compile(model.bind(params),
                                   name=f"braggnn_trigger_img{img}")

    # the deployment contract: full-capacity binding (K = max K_i) blows
    # the U280 DSP pool at img=11, so — like the paper — the deployed
    # schedule caps unrolling at device capacity (4 DSP units per
    # unrolled lane) and must then PASS the part check
    full_check = design.check_budget(part="alveo_u280")
    log.info("full-capacity: %s", full_check.summary())
    if full_check.passed:
        deployed = design
    else:
        deployed = design.with_config(
            hls.CompilerConfig(unroll_factor=trigger.alveo_u280.dsp // 4))
    part_check = deployed.check_budget(part="alveo_u280")
    log.info("deployed: %s", part_check.summary())
    part_check.raise_if_failed()

    out: dict = {"model": f"braggnn_s1_img{img}", "frames": n_frames,
                 "frame_rate_hz": rate_hz, "window": window,
                 "deadline_us": DEADLINE_US,
                 "sample_latency_us": deployed.sample_latency_us,
                 "full_capacity_check": full_check.to_json(),
                 "budget_check": part_check.to_json()}
    out["stream"] = run_stream(deployed, img=img, n_frames=n_frames,
                               rate_hz=rate_hz, window=window)
    return out


if __name__ == "__main__":
    import json
    obs.setup_logging()
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    print(json.dumps(main(fast=True), indent=1))
