"""Continuous-batching serving of a (reduced) Mixtral-style MoE with SWA —
expert routing + rolling-window KV cache through the public engine API.

    PYTHONPATH=src python examples/serve_moe.py
"""

import time

import jax

from repro import obs
from repro.configs import registry
from repro.nn import module, transformer
from repro.serving.engine import ServingEngine

log = obs.get_logger(__name__)


def main() -> None:
    obs.setup_logging()
    cfg = registry.get_tiny("mixtral-8x7b")
    params = module.init_tree(transformer.model_specs(cfg),
                              jax.random.key(0))
    engine = ServingEngine(cfg, params, max_batch=4, max_len=96)

    key = jax.random.key(1)
    n_requests = 10
    for i in range(n_requests):
        k = jax.random.fold_in(key, i)
        n = 3 + int(jax.random.randint(k, (), 0, 10))
        prompt = jax.random.randint(k, (n,), 1, cfg.vocab_size).tolist()
        engine.submit(prompt, max_new_tokens=12)

    t0 = time.monotonic()
    finished = engine.run_until_drained()
    dt = time.monotonic() - t0
    s = engine.stats()
    log.info("%s: %s requests / %s tokens in %.1fs "
             "(%.1f tok/s, 4 lanes, continuous batching)",
             cfg.name, s["requests"], s["generated_tokens"], dt,
             s["generated_tokens"] / dt)
    assert len(finished) == n_requests
    assert all(len(r.output) == 12 for r in finished)
    log.info("sample output: %s", finished[0].output)


if __name__ == "__main__":
    from repro.core.cachedir import enable_compile_cache
    enable_compile_cache()
    main()
