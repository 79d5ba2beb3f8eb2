"""The engine entry: single-sample requests, open loop, through ``DesignEngine``.

``design.engine(...)`` warms every bucket at boot.  Set-up then dispatches
one batch of each bucket's size before the dispatcher starts, which
compiles every bucket's program where the boot's call did not (with the
program's tracing on, its first call runs an unjitted twin), and a short
burst runs the threaded dispatcher once.  In the window this
thread submits request ``i`` at its due time ``t0 + schedule[i]`` whether or
not earlier ones have finished, then waits for the last answer (a minute
past the close at most).  ``request_p50_ms`` is the median over all
requests of the window, each timed from its due time to completion.
"""

from __future__ import annotations

import time

import numpy as np

from bench.feed import DetectorFeed, bursty_schedule

#: longest wait for answers after the window closes
DRAIN_S = 60.0


def run(ctx, design) -> dict:
    tr = ctx.traffic
    t0 = time.perf_counter()
    feed = DetectorFeed(img=ctx.cfg["img"], seed=ctx.seed, **tr["feed"])
    pool = feed.render(tr["pool_frames"])
    base, burst = float(tr["base_rate"]), float(tr["burst_rate"])
    # as many requests as the mean rate fills the window with
    every, blen = tr["burst_every"], tr["burst_len"]
    mean_gap = (blen / burst + (every - blen) / base) / every
    n = int(round(ctx.seconds / mean_gap))
    sched = bursty_schedule(n, base, burst, every, blen, ctx.seed)
    ctx.part("render_frames", t0)

    t0 = time.perf_counter()
    eng = design.engine(backend=ctx.backend, fmt=ctx.fmt_key,
                        max_batch=tr["max_batch"],
                        max_delay_ms=tr["max_delay_ms"])
    ctx.part("first_call_compile", t0)
    t0 = time.perf_counter()
    for b in eng.buckets:
        eng.submit_many(pool[:b])
        eng.run_until_drained()
    eng.start()
    warm = eng.submit_many(pool[:3 * tr["max_batch"]])
    for r in warm:
        r.wait(timeout=DRAIN_S)
    rep0 = eng.report()
    done0, disp0 = rep0.completed, dict(rep0.batch_hist)
    ctx.part("warm_up", t0)

    reqs, due = [], np.empty(n)
    t_start = time.monotonic()
    ctx.window_start()
    for i in range(n):
        due[i] = t_start + sched[i]
        delay = due[i] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        reqs.append(eng.submit(pool[i % len(pool)]))
    t_close = time.monotonic()
    for r in reqs:
        try:
            r.wait(max(0.0, t_close + DRAIN_S - time.monotonic()))
        except Exception:  # noqa: BLE001 - a lost or failed request is
            pass           # counted below, by its state
    ctx.window_end()
    eng.stop()

    ok = [i for i, r in enumerate(reqs) if r.ready and r.error is None]
    # a request that failed or never came counts as late as it was waited for
    done_t = np.full(n, t_close + DRAIN_S)
    done_t[ok] = [reqs[i].done_t for i in ok]
    lat_ms = (done_t - due) * 1e3
    queued_ms = np.array([(reqs[i].start_t - reqs[i].submit_t) * 1e3
                          for i in ok])
    outputs = (np.stack([np.asarray(reqs[i].result[k]).reshape(-1)
                         for i in ok for k in reqs[i].result])
               if ok else np.zeros((0, 2), np.float32))
    rep = eng.report()
    hist = {b: c - disp0.get(b, 0) for b, c in rep.batch_hist.items()}
    return {
        "attempted": n,
        "failed": n - len(ok),
        "e2e": {"request_p50_ms": float(np.percentile(lat_ms, 50))},
        "inputs": pool,
        "input_index": np.asarray(ok, dtype=np.int64) % len(pool),
        "outputs": outputs,
        "missing": sum(1 for r in reqs if not r.ready),
        "state": eng,
        "records": {"queued_ms": queued_ms, "latency_ms": lat_ms,
                    "gen_late_s": np.array([r.submit_t for r in reqs]) - due,
                    "completed": rep.completed - done0,
                    "dispatched_slots": sum(b * c for b, c in hist.items()),
                    "calls": sum(hist.values()), "batch_hist": hist},
    }
