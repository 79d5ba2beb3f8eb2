"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
through the full production stack (pipeline -> train_step -> checkpoints ->
fault-tolerant driver), with a mid-run injected failure to demonstrate
checkpoint/restart.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""

import argparse
import time

import jax

from repro import obs
from repro.checkpoint.ckpt import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data.pipeline import DataConfig, SyntheticTokenPipeline
from repro.launch.steps import make_train_step
from repro.nn import module, transformer
from repro.optim import adamw
from repro.runtime.fault import DriverConfig, FailureInjector, TrainingDriver

log = obs.get_logger(__name__)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()
    obs.setup_logging()

    # ~100M params: 8L x d512 GQA + gated MLP + 32k vocab
    cfg = ModelConfig(
        name="lm-100m", family="dense", n_layers=8, d_model=768,
        n_heads=12, n_kv_heads=4, d_ff=2048, vocab_size=32768,
        attn_pattern=("global",), head_dim=64, attn_block_size=256,
        remat="full")
    specs = transformer.model_specs(cfg)
    n = module.param_count(specs)
    log.info("model: %.1fM params", n / 1e6)

    params = module.init_tree(specs, jax.random.key(0))
    opt = adamw.init_state(params)
    step = jax.jit(make_train_step(
        cfg, adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=20,
                               total_steps=args.steps)),
        donate_argnums=(0, 1))
    pipe = SyntheticTokenPipeline(DataConfig(
        seq_len=args.seq, global_batch=args.batch,
        vocab_size=cfg.vocab_size))
    driver = TrainingDriver(
        DriverConfig(total_steps=args.steps, checkpoint_every=50),
        train_step=step, pipeline=pipe,
        ckpt=CheckpointManager(args.ckpt, keep=2),
        injector=FailureInjector((args.steps // 2,)))   # mid-run crash

    t0 = time.monotonic()
    report = driver.run(params, opt)
    dt = time.monotonic() - t0
    toks = args.steps * args.batch * args.seq
    log.info("done: %s steps, %.0f tok/s, restarts=%s (1 injected), "
             "stragglers=%s", args.steps, toks / dt, report.restarts,
             len(report.straggler_steps))
    log.info("loss: %.3f -> %.3f (next-token CE on synthetic Zipf stream)",
             report.losses[0], report.losses[-1])
    assert report.losses[-1] < report.losses[0]


if __name__ == "__main__":
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    main()
