"""The offline entry: a closed loop of large batches through ``Design.serve``.

A pool of ``pool_batches`` batches of ``batch`` detector frames is rendered
from the seed and cycled.  ``Design.serve`` warms on its first batch and
serves it again; the window starts once that batch's outputs are on the
host, and the batch source stops handing out batches when ``seconds`` have
passed, which closes the window.  Every batch's outputs reach the host
(``np.asarray`` in ``on_batch``), and ``samples_per_s`` is the samples
whose outputs reached the host in the window over the window's length.
"""

from __future__ import annotations

import time

import numpy as np

from bench.feed import DetectorFeed


def run(ctx, design) -> dict:
    tr = ctx.traffic
    bsz, nb = tr["batch"], tr["pool_batches"]
    t0 = time.perf_counter()
    feed = DetectorFeed(img=ctx.cfg["img"], seed=ctx.seed, **tr["feed"])
    pool = feed.render(bsz * nb)
    batches = [pool[i * bsz:(i + 1) * bsz] for i in range(nb)]
    ctx.part("render_frames", t0)

    state = {"t_setup": time.perf_counter(), "t0": None, "t_end": None,
             "served": [], "outputs": [], "t_last": None}

    def source():
        yield batches[0]
        i = 1
        while time.perf_counter() < state["t_end"]:
            state["served"].append(i % nb)
            yield batches[i % nb]
            i += 1
        ctx.window_end()

    def on_batch(i, out):
        (arr,) = out.values()
        host = np.asarray(arr).reshape(bsz, -1)
        now = time.perf_counter()
        if i == 0:
            # serve's warm-up call and the first batch are set-up
            ctx.part("first_call_compile_and_warm_up", state["t_setup"])
            state["t0"] = ctx.window_start()
            state["t_end"] = state["t0"] + ctx.seconds
            return
        state["outputs"].append(host)
        state["t_last"] = now

    rep = design.serve(source(), backend=ctx.backend, fmt=ctx.fmt_key,
                       on_batch=on_batch)
    ctx.window_end()

    n_done = len(state["outputs"]) * bsz
    span = (state["t_last"] - state["t0"]) if state["t_last"] else 0.0
    served = np.asarray(state["served"], dtype=np.int64)
    index = (served[:, None] * bsz + np.arange(bsz)[None, :]).reshape(-1)
    outputs = (np.concatenate(state["outputs"]) if state["outputs"]
               else np.zeros((0, 2), np.float32))
    return {
        "attempted": len(served) * bsz,
        "failed": len(served) * bsz - n_done,
        "e2e": {"samples_per_s": n_done / span if span > 0 else 0.0},
        "inputs": pool,
        "input_index": index[:len(outputs)],
        "outputs": outputs,
        "missing": len(served) * bsz - n_done,
        "state": rep,
        "records": {"calls": len(state["outputs"]), "batch": bsz,
                    "samples": n_done, "window_s": span},
    }
