"""Quickstart: the OpenHLS pipeline end to end on one convolution.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --pipeline cse,dce

One ``repro.hls.compile()`` call runs the whole Fig. 1 flow: the conv2d
loop nest is symbolically interpreted into an SSA DFG (store-load
forwarding included), optimised, scheduled, and returned as a ``Design``
handle.  We then behaviourally verify it, quantise to FloPoCo (5,4), and
run the design's compiled rendering.  ``--pipeline`` selects which registered
passes run (comma-separated, in order) instead of the default §3.2
pipeline.
"""

import argparse

import numpy as np

import repro.hls as hls
from repro import obs
from repro.core import FP_5_4, frontend
from repro.core.pipeline import DEFAULT_PIPELINE, parse_pipeline_spec

log = obs.get_logger(__name__)


def build(ctx) -> None:
    # 1. describe the DNN operation as an scf-style loop nest
    x = ctx.memref("input", (1, 3, 16, 16), "input")
    w = ctx.memref("weight", (8, 3, 3, 3), "weight")
    b = ctx.memref("bias", (8,), "weight")
    out = ctx.memref("out", (1, 8, 14, 14), "output")
    frontend.conv2d(ctx, x, w, b, out)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pipeline", default=None, metavar="P1,P2,...",
                    help="comma-separated pass pipeline "
                         f"(default: {','.join(DEFAULT_PIPELINE)})")
    args = ap.parse_args(argv)
    obs.setup_logging()
    try:
        config = hls.CompilerConfig() if args.pipeline is None else \
            hls.CompilerConfig(pipeline=parse_pipeline_spec(args.pipeline))
    except ValueError as e:
        raise SystemExit(str(e))

    # 2. compile: trace -> passes -> schedule, one public entrypoint
    design = hls.compile(build, name="conv2d_quickstart", config=config)
    log.info("%s", design.report())

    # 3. one behavioural testbench covers it all (§3.2): optimised DFG and
    # compiled rendering vs the interpreter reference, plus the FloPoCo
    # (5,4) functional model
    report = design.verify(batch=4, seed=0, fmt=FP_5_4)
    log.info("%s", report.summary())
    log.info("(5,4) max abs deviation vs fp32: %.4f",
             report.max_abs_err_quant)
    assert report.passed, "behavioural verification failed"
    log.info("compiled rendering matches the functional "
             "simulation  [OK]")

    # 4. the deployable path: run a fresh batch through the compiled design
    fn = design.jax_fn()
    from repro.core import verify
    feeds = verify.random_feeds(design.graph_opt, batch=4, seed=1)
    got = np.asarray(fn(feeds)["out"])
    log.info("served a batch of 4 through the compiled design: out %s",
             got.shape)

    # 5. a second compile of the same program is a cache hit
    hls.compile(build, name="conv2d_quickstart", config=config,
                session=design.session)
    stats = design.session.stats()
    log.info("design cache: %s hit(s), %s miss(es), hash %s",
             stats["hits"], stats["misses"], design.design_hash[:12])


if __name__ == "__main__":
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    main()
