"""BraggNN low-latency inference — the paper's deployment scenario (§4.2).

    PYTHONPATH=src python examples/braggnn_serve.py
    PYTHONPATH=src python examples/braggnn_serve.py --tuned
    PYTHONPATH=src python examples/braggnn_serve.py --pipeline cse,dce
    PYTHONPATH=src python examples/braggnn_serve.py --engine --save b.design
    PYTHONPATH=src python examples/braggnn_serve.py --engine --load b.design

Trains BraggNN briefly on synthetic Bragg peaks, binds the trained weights
into the declarative module graph (``models.braggnn.build``), and compiles
it through the public API — ``repro.hls.compile`` auto-lowers the module
to the paper's loop nests via the bridge (bit-identical to the hand-
written ``frontend.braggnn``).  Batched peak-localisation requests are
then served through ``Design.serve`` on the design's compiled rendering
(the nest tier's registry kernels) — at (5,4) by default, or whatever
format the tuned candidate carries.

``--tuned`` auto-loads the best known compile configuration from the
persistent ``TuningDB`` via ``Design.apply_tuned`` (populate it with
``python -m repro.tune --config braggnn``; a miss names the DB path it
probed); ``--pipeline`` overrides the pass pipeline by hand.  Designs are
cached under the shared versioned cache root (``cache=True``), so warm
runs serve the schedule from disk.

``--engine`` additionally fronts the design with the async adaptive-
batching engine (``Design.engine``) and prints its tail-latency summary;
``--save PATH`` persists the warm-boot artifact, ``--load PATH`` boots
from one instead of training + compiling (and is the engine's replica-
restart source).

``--trace-out PATH`` turns on :mod:`repro.obs` for the whole run and
exports the compile-and-serve timeline as Chrome-trace JSON (open in
``chrome://tracing`` or summarise with ``python -m repro.obs PATH``).
"""

import argparse
import time

import jax
import jax.numpy as jnp

import repro.hls as hls
from repro import obs
from repro.core.pipeline import parse_pipeline_spec
from repro.models import braggnn
from repro.optim import adamw

log = obs.get_logger(__name__)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tuned", action="store_true",
                    help="load the best compile config from the TuningDB")
    ap.add_argument("--pipeline", default=None, metavar="P1,P2,...",
                    help="override the pass pipeline (comma-separated)")
    ap.add_argument("--db", default=None,
                    help="TuningDB path (default: shared cache root)")
    ap.add_argument("--engine", action="store_true",
                    help="also serve through the async adaptive-batching "
                         "engine and print its tail-latency summary")
    ap.add_argument("--save", default=None, metavar="PATH",
                    help="persist the warm-boot artifact (Design.save)")
    ap.add_argument("--load", default=None, metavar="PATH",
                    help="boot from a saved artifact instead of "
                         "training + compiling (hls.load)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable repro.obs and export the run's "
                         "Chrome-trace JSON to PATH")
    return ap.parse_args(argv)


def train(model: hls.ModuleGraph, steps: int = 150) -> dict:
    """Brief synthetic-peak training run; returns the trained param tree."""
    params = model.init_params(jax.random.key(0))
    opt_cfg = adamw.AdamWConfig(peak_lr=2e-3, warmup_steps=10,
                                total_steps=steps, weight_decay=0.0)
    state = adamw.init_state(params)

    @jax.jit
    def step(p, s, x, y):
        def loss(pp):
            return jnp.mean((braggnn.forward(pp, x) - y * 10.0) ** 2)
        l, g = jax.value_and_grad(loss)(p)
        p2, s2, _ = adamw.apply_updates(opt_cfg, p, g, s)
        return p2, s2, l

    key = jax.random.key(1)
    for i in range(steps):
        x, y = braggnn.synthetic_peaks(jax.random.fold_in(key, i), 64)
        params, state, l = step(params, state, x, y)
    log.info("trained BraggNN: loss %.4f", float(l))
    return params


def serve_engine(design, serve_fmt, save_path=None) -> None:
    """Front the design with the async engine; print the tail-latency
    summary (and where a poisoned replica would warm-boot from)."""
    x, y = braggnn.synthetic_peaks(jax.random.key(7), 256)
    samples = jnp.asarray(x)[:, None]            # (N, 1, img, img) memrefs
    eng = design.engine(fmt=serve_fmt, max_batch=16, max_delay_ms=2.0,
                        artifact_path=save_path)
    with eng:
        reqs = [eng.submit(s) for s in samples]
        for r in reqs:
            r.wait(timeout=60)
    log.info("engine: %s", eng.report().summary())


def main(argv=None) -> None:
    args = parse_args(argv)
    obs.setup_logging()
    if args.trace_out:
        obs.enable()

    try:
        _run(args)
    finally:
        if args.trace_out:
            path = obs.export_chrome_trace(args.trace_out)
            log.info("obs: exported Chrome trace to %s "
                     "(chrome://tracing, or `python -m repro.obs %s`)",
                     path, path)


def _run(args) -> None:
    if args.load:
        # --- warm boot: one disk read, no training, no compile -------------
        t0 = time.perf_counter()
        design = hls.load(args.load)
        log.info("warm boot from %s: %.2fs (%s, hash %s)", args.load,
                 time.perf_counter() - t0, design.name,
                 design.design_hash[:12])
        serve_fmt = design.manifest.get("fmt")
        if args.engine:
            serve_engine(design, serve_fmt, save_path=args.load)
        else:
            x, _ = braggnn.synthetic_peaks(jax.random.key(7), 1024)
            log.info("%s", design.serve([x] * 10, fmt=serve_fmt).summary())
        return

    # --- describe once, train, bind ----------------------------------------
    model = braggnn.build(s=1)
    model = model.bind(train(model))

    # --- compile through the public API (shared on-disk design cache) ------
    config, serve_fmt, source = hls.CompilerConfig(n_stages=3), "5_4", \
        "default"
    if args.pipeline is not None:
        try:
            names = parse_pipeline_spec(args.pipeline)
        except ValueError as e:
            raise SystemExit(str(e))
        config = hls.CompilerConfig(pipeline=names, n_stages=3)
        source = f"--pipeline {','.join(names) or '(none)'}"

    tuned_space = db = None
    if args.tuned:
        from repro.tune import TuningDB, braggnn_space
        tuned_space = braggnn_space()
        db = TuningDB(args.db) if args.db else None
    t0 = time.perf_counter()
    # the tuned config (if any) is resolved before the single compile; a
    # TuningDB miss prints which DB path was probed
    design = hls.compile(model, name="braggnn_s1", config=config,
                         cache=True, tuned=tuned_space, db=db)
    if design.tuned_candidate is not None:
        fmt = design.tuned_candidate.get("precision", "5_4")
        serve_fmt = None if fmt == "fp32" else fmt
        source = f"tuned ({design.tuned_candidate.label()})"
    compile_s = time.perf_counter() - t0

    # report the latency of the configuration actually deployed: stage II
    # when the config pipelines, plain makespan when it does not
    stage = (f"{design.config.n_stages}-stage II={design.stage_ii}"
             if design.stage_ii is not None else "unpipelined")
    served_from = "cache" if design.session.stats()["hits"] else \
        "cold compile"
    log.info("OpenHLS schedule [%s] (%s, %.1fs): %s intervals total, "
             "%s -> %.2f us/sample "
             "(paper: 1238 total, 3-stage II=480 -> 4.8 us/sample)",
             source, served_from, compile_s, design.makespan, stage,
             design.sample_latency_us)

    # --- serve batches at the deployed precision ---------------------------
    x, y = braggnn.synthetic_peaks(jax.random.key(7), 1024)
    report = design.serve([x] * 10, fmt=serve_fmt, collect=True)
    (pred,) = report.outputs[-1].values()
    pred = jnp.reshape(pred, y.shape)
    err_px = float(jnp.mean(jnp.abs(pred / 10.0 - y))) * 11
    log.info("%s; mean localisation error %.3f px", report.summary(),
             err_px)

    # --- warm-boot artifact + async engine ---------------------------------
    if args.save:
        path = design.save(args.save, fmt=serve_fmt)
        log.info("saved warm-boot artifact: %s (%s bytes)", path,
                 f"{path.stat().st_size:,}")
    if args.engine:
        serve_engine(design, serve_fmt, save_path=args.save)


if __name__ == "__main__":
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    main()
