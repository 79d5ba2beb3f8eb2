"""Paper Fig. 7 + §4.2 precision study on a TRAINED BraggNN.

Trains BraggNN on synthetic Bragg peaks (Gaussian blobs), then:
  * histograms the trained weight exponents (Fig. 7) and derives the
    smallest sufficient wE;
  * sweeps (5,11)/(5,4)/(5,3) weight+activation quantisation and reports
    localisation error vs fp32 — the accuracy evidence behind the paper's
    precision choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.precision import (FORMATS, exponent_histogram,
                                  required_exponent_bits)
from repro.models import braggnn
from repro.nn import module
from repro.optim import adamw

log = obs.get_logger(__name__)


def train(steps: int = 300, img: int = 11, batch: int = 64):
    sp = braggnn.specs(1, img)
    params = module.init_tree(sp, jax.random.key(0))
    opt_cfg = adamw.AdamWConfig(peak_lr=2e-3, warmup_steps=20,
                                total_steps=steps, weight_decay=0.0)
    state = adamw.init_state(params)

    def loss_fn(p, x, y):
        return jnp.mean((braggnn.forward(p, x) - y * 10.0) ** 2)

    @jax.jit
    def step(p, s, x, y):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        p2, s2, _ = adamw.apply_updates(opt_cfg, p, g, s)
        return p2, s2, l

    key = jax.random.key(1)
    losses = []
    for i in range(steps):
        x, y = braggnn.synthetic_peaks(jax.random.fold_in(key, i), batch,
                                       img=img)
        params, state, l = step(params, state, x, y)
        losses.append(float(l))
    return params, losses


def run(steps: int = 300) -> dict:
    params, losses = train(steps)
    hist = exponent_histogram(params)
    out = {
        "loss_first": losses[0], "loss_last": losses[-1],
        "exp_min": min(hist), "exp_max": max(hist),
        "required_we_100": required_exponent_bits(hist, 1.0),
        "required_we_999": required_exponent_bits(hist, 0.999),
        "hist": hist,
    }
    # accuracy sweep
    x, y = braggnn.synthetic_peaks(jax.random.key(99), 256)
    ref = braggnn.forward(params, x)
    err_ref = float(jnp.mean(jnp.abs(ref / 10.0 - y)))
    out["pixel_err_fp32"] = err_ref * 11
    for key in ("5_11", "5_4", "5_3"):
        pred = braggnn.forward(params, x, fmt=key)
        out[f"pixel_err_{key}"] = float(
            jnp.mean(jnp.abs(pred / 10.0 - y))) * 11
    return out


def main(print_csv: bool = True, steps: int = 300) -> dict:
    out = run(steps)
    if print_csv:
        log.info("# trained %s steps: loss %.3f -> %.4f", steps,
                 out["loss_first"], out["loss_last"])
        log.info("# weight exponents in [%s, %s] -> required wE=%s "
                 "(99.9%%: %s) — paper keeps wE=5", out["exp_min"],
                 out["exp_max"], out["required_we_100"],
                 out["required_we_999"])
        print("format,mean_pixel_error")
        print(f"fp32,{out['pixel_err_fp32']:.4f}")
        for key in ("5_11", "5_4", "5_3"):
            print(f"({key.replace('_', ',')}),{out[f'pixel_err_{key}']:.4f}")
    return out


if __name__ == "__main__":
    obs.setup_logging()
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    main()
