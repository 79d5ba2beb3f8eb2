#!/usr/bin/env python3
"""Bring-up smoke run: BraggNN through the main path on one TPU chip.

    python3 chip_smoke.py

BraggNN(s=1, img=11), the paper's model at its full width, is trained for a
few steps on seeded synthetic Bragg peaks and then driven once through the
entry points a user calls, in one process:

1. ``hls.compile(module)`` builds the ``Design``;
2. ``design.jax_fn()`` runs the nest-pattern tier with
   Mosaic-compiled ``pl.pallas_call`` kernels, in fp32 and at
   ``fmt="5_4"``, checked against a float32 ``jax.numpy`` reference and
   against the functional simulator ``emit.evaluate``;
3. ``design.engine()`` answers requests in every warmed
   bucket;
4. ``design.trigger(window=4)`` decides a seeded
   ``DetectorFeed`` twice, and the two runs must agree exactly;
5. BraggNN(s=4), the original widths (64 -> 32 -> 8 channels, flattening
   to 200), is trained the same way, compiled with ``hls.compile`` and
   served once through ``design.serve`` in fp32 and at
   ``fmt="5_4"``, checked against the float32 reference.

Compile seconds and warm µs/sample at batch 64 are printed for
information; they are host-clock readings of one run, not a benchmark.

Any failed check raises, so the script exits non-zero.  It also exits
non-zero, printing no result, when JAX finds no TPU.  On success the last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
IMG = 11
BATCH = 64            #: the batch of the reference comparison and timing
N_EVAL = 8            #: samples compared against emit.evaluate (slow numpy)
TRAIN_STEPS = 100
TRIGGER_FRAMES = 200

#: fp32 design vs the float32 jnp reference.  The design's NLB softmax is
#: the paper's order-8 Taylor exp, the reference's is the true exp; on
#: outputs of a few units that differs by about 1e-4.  The fp32 kernels
#: contract on the MXU at full fp32 precision.
TOL_FP32_VS_REF = 2e-3
#: fp32 design vs emit.evaluate, the same Taylor functional model in numpy
#: fp32: only the summation order differs.
TOL_FP32_VS_EVAL = 1e-3
#: (5,4) design vs the fp32 reference, relative to the largest reference
#: output.  (5,4) keeps 4 fraction bits, so each rounding moves a value by
#: up to 2**-5 (3.1%).  The nest tier rounds every kernel's operands and
#: results, and nine kernels sit in series, so a few percent is expected
#: and 15% bounds it with margin.
TOL_54_VS_REF_REL = 0.15

#: the plan the chip must show (``use_pallas``, ``interpret``)
EXPECT_PLAN = (True, False)


def check(cond: bool, msg: str) -> None:
    """Fail the run: an assert would vanish under ``python -O``."""
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    """The device JAX runs on; exits non-zero unless it is a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX runs on {dev.platform!r}); "
                 f"this script runs only on the chip")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def train(model, steps: int):
    """A short seeded training run, so outputs are peak positions and not
    the near-zero outputs of a random init."""
    import jax
    import jax.numpy as jnp

    from repro.models import braggnn
    from repro.optim import adamw

    params = model.init_params(jax.random.key(SEED))
    cfg = adamw.AdamWConfig(peak_lr=2e-3, warmup_steps=10,
                            total_steps=steps, weight_decay=0.0)
    state = adamw.init_state(params)

    @jax.jit
    def step(p, s, x, y):
        def loss(pp):
            return jnp.mean((braggnn.forward(pp, x) - y * 10.0) ** 2)
        val, g = jax.value_and_grad(loss)(p)
        p2, s2, _ = adamw.apply_updates(cfg, p, g, s)
        return p2, s2, val

    key = jax.random.key(SEED + 1)
    for i in range(steps):
        x, y = braggnn.synthetic_peaks(jax.random.fold_in(key, i), 64,
                                       img=IMG)
        params, state, val = step(params, state, x, y)
    log(f"train: {steps} steps, loss {float(val):.4f}")
    return params


def check_plan(plan, fmt) -> None:
    log(f"plan[{fmt or 'fp32'}]: {plan.summary()}")
    check((plan.use_pallas, plan.interpret) == EXPECT_PLAN,
          f"plan use_pallas/interpret {(plan.use_pallas, plan.interpret)} "
          f"!= {EXPECT_PLAN}")
    check(plan.mode == "nests", f"plan mode {plan.mode}")
    check(not plan.fallbacks, f"fallbacks {plan.fallbacks}")
    for kname in ("conv2d_vmem", "smallfloat_matmul", "fused_softmax"):
        check(any(k.startswith(kname) for k in plan.kernels),
              f"{kname} unused: {plan.kernels}")


def out_array(out: dict, batch: int):
    import numpy as np
    (arr,) = out.values()
    return np.asarray(arr).reshape(batch, -1)


def phase_jax_fn(design, params, x):
    """Nest tier, fp32 and (5,4), vs the jnp reference and emit.evaluate.
    Returns the fp32 callable, its feeds and its outputs."""
    import jax
    import numpy as np

    from repro.models import braggnn

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(braggnn.forward)(params, x))
    ev = out_array(design.run(np.asarray(x[:N_EVAL])), N_EVAL)
    scale = float(np.max(np.abs(ref)))
    log(f"reference: float32 braggnn.forward at highest precision, "
        f"max |out| {scale:.4f}")
    check(scale > 0.1, "degenerate reference outputs")

    for fmt in (None, "5_4"):
        fn = design.jax_fn(fmt=fmt)
        check_plan(fn.plan, fmt)
        feeds = design.feeds(np.asarray(x))
        t0 = time.perf_counter()
        got = out_array(jax.block_until_ready(fn(feeds)), BATCH)
        log(f"compile[{fmt or 'fp32'}]: first batch-{BATCH} call "
            f"{time.perf_counter() - t0:.2f} s (host clock)")
        check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
        check(bool(np.all(np.isfinite(got))), "non-finite outputs")
        err_ref = float(np.max(np.abs(got - ref)))
        if fmt is None:
            err_ev = float(np.max(np.abs(got[:N_EVAL] - ev)))
            log(f"fp32: max abs err vs reference {err_ref:.3e} "
                f"(tol {TOL_FP32_VS_REF:g}), vs emit.evaluate on "
                f"{N_EVAL} samples {err_ev:.3e} (tol {TOL_FP32_VS_EVAL:g})")
            check(err_ref <= TOL_FP32_VS_REF, "fp32 vs reference")
            check(err_ev <= TOL_FP32_VS_EVAL, "fp32 vs emit.evaluate")
            fp32 = (fn, feeds, got)
        else:
            rel = err_ref / scale
            log(f"(5,4): max abs err vs fp32 reference {err_ref:.3e} = "
                f"{rel:.3%} of max |out| (tol {TOL_54_VS_REF_REL:.0%})")
            check(rel <= TOL_54_VS_REF_REL, "(5,4) vs fp32 reference")
    return fp32


def phase_timing(fn, feeds, reps: int = 20) -> None:
    import jax
    import numpy as np
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(feeds))
        times.append(time.perf_counter() - t0)
    med = float(np.median(times))
    log(f"warm: batch {BATCH}, median of {reps} calls {med * 1e3:.3f} ms "
        f"= {med / BATCH * 1e6:.2f} us/sample (host clock, "
        f"informative only)")


def phase_engine(design, x, want) -> None:
    """One synchronous dispatch per warmed bucket; every request must
    complete, none may drop, and answers must match the direct call."""
    import numpy as np
    eng = design.engine(max_batch=32)
    log(f"engine: served by {eng.report().served}")
    offset = 0
    for bucket in eng.buckets:
        before = (eng.report().completed, eng.report().dropped)
        reqs = eng.submit_many(np.asarray(x[offset:offset + bucket]))
        eng.run_until_drained()
        rep = eng.report()
        done = rep.completed - before[0]
        dropped = rep.dropped - before[1]
        log(f"engine bucket {bucket}: completed {done}, dropped {dropped}")
        check(done == bucket and dropped == 0, f"bucket {bucket}")
        got = np.stack([out_array(r.wait(timeout=60), 1)[0] for r in reqs])
        err = float(np.max(np.abs(got - want[offset:offset + bucket])))
        check(err <= 1e-4, f"bucket {bucket} answers differ by {err:.3e}")
        offset += bucket
    rep = eng.report()
    check(rep.restarts == 0, f"{rep.restarts} replica restarts")
    check(sorted(rep.batch_hist) == list(eng.buckets),
          f"dispatched buckets {rep.batch_hist}")
    log(f"engine: {rep.completed}/{rep.submitted} completed, "
        f"{rep.dropped} dropped, {rep.restarts} restarts")


def phase_trigger(design) -> None:
    from repro.trigger import DetectorFeed

    runs = []
    for i in range(2):
        loop = design.trigger(window=4)
        thr = loop.calibrate(DetectorFeed(img=IMG, seed=SEED + 11), 64)
        rep = loop.run(DetectorFeed(img=IMG, seed=SEED + 11),
                       TRIGGER_FRAMES)
        bits = "".join("A" if d.accept else "r" for d in rep.decisions)
        log(f"trigger run {i}: threshold {thr:.6f}, {rep.accepts} accept / "
            f"{rep.rejects} reject of {rep.processed}, dropped "
            f"{rep.dropped}; decisions {bits}")
        check(rep.processed == TRIGGER_FRAMES and rep.dropped == 0,
              "trigger lost frames")
        check(0 < rep.accepts < TRIGGER_FRAMES, "degenerate accept split")
        runs.append([(d.frame_id, d.accept, d.score) for d in rep.decisions])
    check(runs[0] == runs[1], "same-seed trigger runs disagree")
    log("trigger: the two same-seed runs decide identically")


def phase_wide(hls) -> None:
    """BraggNN(s=4) through ``hls.compile`` and ``Design.serve``, fp32 and
    (5,4), against the float32 reference at the s=1 tolerances."""
    import jax
    import numpy as np

    from repro.models import braggnn

    model = braggnn.build(4, IMG)
    params = train(model, TRAIN_STEPS)
    t0 = time.perf_counter()
    design = hls.compile(model.bind(params), name="braggnn_s4_smoke")
    log(f"hls.compile s=4: {time.perf_counter() - t0:.1f} s (host clock)")
    x, _ = braggnn.synthetic_peaks(jax.random.key(SEED + 8), BATCH, img=IMG)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(braggnn.forward)(params, x))
    scale = float(np.max(np.abs(ref)))
    check(scale > 0.1, "degenerate s=4 reference outputs")
    for fmt in (None, "5_4"):
        rep = design.serve([np.asarray(x)], fmt=fmt, collect=True)
        log(f"s=4 serve[{fmt or 'fp32'}]: {rep.served}")
        check("Mosaic kernels" in rep.served and not rep.fallbacks,
              f"s=4 served by {rep.served}, fallbacks {rep.fallbacks}")
        got = out_array(rep.outputs[0], BATCH)
        check(bool(np.all(np.isfinite(got))), "s=4 non-finite outputs")
        err = float(np.max(np.abs(got - ref)))
        if fmt is None:
            log(f"s=4 fp32: max abs err vs reference {err:.3e} "
                f"(tol {TOL_FP32_VS_REF:g})")
            check(err <= TOL_FP32_VS_REF, "s=4 fp32 vs reference")
        else:
            log(f"s=4 (5,4): max abs err vs fp32 reference {err:.3e} = "
                f"{err / scale:.3%} of max |out| "
                f"(tol {TOL_54_VS_REF_REL:.0%})")
            check(err / scale <= TOL_54_VS_REF_REL,
                  "s=4 (5,4) vs fp32 reference")


def main() -> None:
    device = device_info()
    log(f"device: {device}")

    import jax

    import repro.hls as hls
    from repro.core.cachedir import enable_compile_cache
    from repro.models import braggnn

    log(f"compile cache: {enable_compile_cache()}")
    model = braggnn.build(1, IMG)
    params = train(model, TRAIN_STEPS)
    module = model.bind(params)

    t0 = time.perf_counter()
    design = hls.compile(module, name="braggnn_s1_smoke")
    log(f"hls.compile: {time.perf_counter() - t0:.1f} s (host clock), "
        f"{design.summary()}")

    x, _ = braggnn.synthetic_peaks(jax.random.key(SEED + 7), BATCH, img=IMG)
    fn, feeds, want = phase_jax_fn(design, params, x)
    phase_timing(fn, feeds)
    phase_engine(design, x, want)
    phase_trigger(design)
    phase_wide(hls)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
