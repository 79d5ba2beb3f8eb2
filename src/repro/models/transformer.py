"""A whisper_tiny-shaped transformer encoder block in JAX — the tensor-level
twin of the scalar loop-nest program in
``repro.core.frontend.transformer_encoder_block``.

The block is the million-op scaling target for the compile path (ISSUE: the
default geometry below traces to ~1.7M raw ops) and the first sequence
model through the nn -> loop-nest bridge:

    x = x + Attn(RMS(x));  x = x + MLP(RMS(x));  out = RMS(x)

``forward`` mirrors the DFG's *functional model* — the softmax uses the
paper's Taylor-exp approximation (order-k series with 2^r range reduction),
not ``jax.nn.softmax`` — so the fp32 DFG matches it tightly, not just to
approximation error.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.precision import FORMATS, quantize
from repro.nn import graph as nng
from repro.nn.attention import out_project, qkv_project


def build(seq: int = 16, d_model: int = 64, n_heads: int = 4,
          ffn: int = 256, *, params=None,
          taylor_order: int = 8, eps: float = 1e-5) -> nng.ModuleGraph:
    """The encoder block as a declarative :class:`~repro.nn.graph.ModuleGraph`.

    Node names pin the hand-written
    ``frontend.transformer_encoder_block`` memref/label scheme, so the
    bridged DFG is bit-identical (same ``graph_fingerprint``) to the
    hand-written one.  Defaults are whisper_tiny-shaped but trimmed to a
    16-token window; ``params`` optionally binds a trained tree.
    """
    nodes = [
        nng.Attention("attn", d_model=d_model, n_heads=n_heads,
                      taylor_order=taylor_order, eps=eps),
        nng.MLP("mlp", d_model=d_model, hidden=ffn, eps=eps),
        nng.RMSNorm("ln_post", dim=d_model, eps=eps),
    ]
    return nng.ModuleGraph(
        "encoder_block", (seq, d_model), nodes, params=params,
        meta={"seq": seq, "d_model": d_model, "n_heads": n_heads,
              "ffn": ffn, "taylor_order": taylor_order})


def specs(seq: int = 16, d_model: int = 64, n_heads: int = 4,
          ffn: int = 256) -> dict:
    """The ParamSpec tree (derived from :func:`build` — one description)."""
    return build(seq, d_model, n_heads, ffn).specs()


def taylor_exp(x: jax.Array, *, order: int = 8,
               range_reduce: int = 2) -> jax.Array:
    """exp(x) the way the DFG computes it: k-th order Taylor series on
    x/2^r, squared r times (``Context.exp`` + ``frontend.soft_max``)."""
    z = x * (1.0 / (1 << range_reduce))
    acc = jnp.ones_like(z) + z
    zk = z
    fact = 1.0
    for k in range(2, order + 1):
        zk = zk * z
        fact *= k
        acc = acc + zk * (1.0 / fact)
    for _ in range(range_reduce):
        acc = acc * acc
    return acc


def _softmax_taylor(scores: jax.Array, *, order: int) -> jax.Array:
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = taylor_exp(scores - m, order=order)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _rms(x: jax.Array, gamma: jax.Array, *, eps: float) -> jax.Array:
    # sum * (1/D), matching the DFG's reduction + const-multiply form
    ms = jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / x.shape[-1])
    return x * (1.0 / jnp.sqrt(ms + eps)) * gamma


def forward(params: dict, x: jax.Array, *, n_heads: int,
            taylor_order: int = 8, eps: float = 1e-5,
            fmt: Optional[str] = None) -> jax.Array:
    """x: (B, L, d_model) -> (B, L, d_model).

    fmt: FloPoCo format key ('5_11' | '5_4' | '5_3') — quantises weights
    and inter-layer activations, modelling the reduced-precision datapath
    (coarser than the DFG's per-op functional model, so quantised
    comparisons need the loose BraggNN-style tolerances).
    """
    q = (lambda a: quantize(a, FORMATS[fmt])) if fmt else (lambda a: a)
    p = jax.tree_util.tree_map(q, params)
    x = q(jnp.asarray(x, dtype=jnp.float32))

    # --- attention sub-block ------------------------------------------------
    h = q(_rms(x, p["attn"]["norm"]["gamma"], eps=eps))
    qh, kh, vh = qkv_project(p["attn"], h)                 # (B,L,H,dh)
    dh = qh.shape[-1]
    scores = q(jnp.einsum("bshk,bthk->bhst", qh, kh) / jnp.sqrt(
        jnp.float32(dh)))
    attn = q(_softmax_taylor(scores, order=taylor_order))
    y = q(jnp.einsum("bhst,bthk->bshk", attn, vh))
    x = q(x + q(out_project(p["attn"], y)))

    # --- MLP sub-block ------------------------------------------------------
    h = q(_rms(x, p["mlp"]["norm"]["gamma"], eps=eps))
    h = q(jax.nn.relu(h @ p["mlp"]["fc1"]["w"].T + p["mlp"]["fc1"]["b"]))
    h = q(h @ p["mlp"]["fc2"]["w"].T + p["mlp"]["fc2"]["b"])
    x = q(x + h)

    return q(_rms(x, p["ln_post"]["gamma"], eps=eps))
