"""BraggNN in JAX (paper Listing 5) — the production tensor-level twin of
the scalar loop-nest program in ``repro.core.frontend.braggnn``.

Used two ways:
  * as the oracle the scalar DFG and its compiled rendering are
    behaviourally verified against;
  * as a trainable model (QAT with ``ste_quantize``) for the precision
    study (paper Fig. 7).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import FORMATS, quantize
from repro.nn import graph as nng

ACCUM = jnp.float32


def build(s: int = 1, img: int = 11, *, params=None,
          taylor_order: int = 8) -> nng.ModuleGraph:
    """BraggNN(s) as a declarative :class:`~repro.nn.graph.ModuleGraph`.

    The single-source model description: ``.specs()`` is the training
    param tree, and ``repro.hls.compile(build(...))`` auto-lowers it to
    the loop-nest DFG via the bridge.  Node names/prefixes/labels pin the
    hand-written ``frontend.braggnn`` memref scheme, so the bridged DFG is
    bit-identical (same ``graph_fingerprint``) to the hand-written one.
    ``params`` optionally binds a trained param tree for serving.
    """
    c1, c2 = 16 * s, 8 * s
    h3 = img - 6
    n_flat = 2 * s * h3 * h3
    dims = [n_flat, 16 * s, 8 * s, 4 * s, 2]
    nodes = [
        nng.Conv2d("conv1", in_channels=1, out_channels=c1, kernel=3,
                   out_name_="feat", label_="cnn_layers_1"),
        nng.NonLocalBlock("nlb", channels=c1, mid_channels=c2,
                          taylor_order=taylor_order),
        nng.ReLU(out_name_="cnn2_relu0", label_="cnn_layers_2.relu0"),
        nng.Conv2d("conv2a", in_channels=c1, out_channels=c2, kernel=3,
                   prefix_="cnn2.conv1", out_name_="cnn2_conv1",
                   label_="cnn_layers_2.conv1"),
        nng.ReLU(out_name_="cnn2_relu1", label_="cnn_layers_2.relu1"),
        nng.Conv2d("conv2b", in_channels=c2, out_channels=2 * s, kernel=3,
                   prefix_="cnn2.conv2", out_name_="cnn2_conv2",
                   label_="cnn_layers_2.conv2"),
        nng.ReLU(out_name_="cnn2_relu2", label_="cnn_layers_2.relu2"),
        nng.Flatten(out_name_="flat"),
    ]
    for li in range(4):
        nodes.append(nng.Linear(
            f"dense{li}", in_features=dims[li], out_features=dims[li + 1],
            prefix_=f"dense.{li}", out_name_=f"dense_{li}_out",
            label_=f"dense.{li}"))
        if li < 3:
            nodes.append(nng.ReLU(out_name_=f"dense_{li}_relu",
                                  label_=f"dense.{li}.relu"))
    nodes.append(nng.OutputReLU(label_="dense.final_relu"))
    return nng.ModuleGraph(
        "braggnn", (1, 1, img, img), nodes, params=params,
        meta={"s": s, "img": img})


def specs(s: int = 1, img: int = 11) -> dict:
    """The ParamSpec tree (derived from :func:`build` — one description)."""
    return build(s, img).specs()


def _conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array]) -> jax.Array:
    """Valid-padding NCHW conv (matches the loop-nest semantics)."""
    y = jax.lax.conv_general_dilated(
        x.astype(ACCUM), w.astype(ACCUM), window_strides=(1, 1),
        padding="VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if b is not None:
        y = y + b.astype(ACCUM)[None, :, None, None]
    return y


def forward(params: dict, x: jax.Array, *, s: int = 1,
            fmt: Optional[str] = None) -> jax.Array:
    """x: (B, 1, img, img) -> (B, 2) peak centre estimates.

    fmt: FloPoCo format key ('5_11' | '5_4' | '5_3') — quantises weights
    *and* inter-layer activations, modelling the paper's reduced-precision
    datapath end to end.
    """
    q = (lambda a: quantize(a, FORMATS[fmt])) if fmt else (lambda a: a)
    p = jax.tree_util.tree_map(q, params)

    feat = q(_conv(x, p["conv1"]["w"], p["conv1"]["b"]))       # (B,c1,9,9)
    b, c1, h, w = feat.shape
    n = h * w

    def conv1x1(name):
        return q(_conv(feat, p["nlb"][name]["w"], None))       # (B,c2,9,9)

    theta, phi, g = conv1x1("theta"), conv1x1("phi"), conv1x1("g")
    c2 = theta.shape[1]
    tf = theta.reshape(b, c2, n)
    pf = phi.reshape(b, c2, n)
    gf = g.reshape(b, c2, n)
    scores = q(jnp.einsum("bci,bcj->bij", tf, pf))             # (B,n,n)
    attn = jax.nn.softmax(scores, axis=-1)
    y = q(jnp.einsum("bij,bcj->bci", attn, gf)).reshape(b, c2, h, w)
    z = q(_conv(y, p["nlb"]["out"]["w"], None))
    feat = q(feat + z)

    r = jax.nn.relu(feat)
    r = jax.nn.relu(q(_conv(r, p["conv2a"]["w"], p["conv2a"]["b"])))
    r = jax.nn.relu(q(_conv(r, p["conv2b"]["w"], p["conv2b"]["b"])))
    flat = r.reshape(b, -1)
    for li in range(4):
        d = p[f"dense{li}"]
        flat = q(jnp.einsum("bk,nk->bn", flat, d["w"].astype(ACCUM))
                 + d["b"].astype(ACCUM))
        flat = jax.nn.relu(flat)
    return flat


def params_from_feeds(feeds: dict[str, np.ndarray], s: int = 1) -> dict:
    """Adapt the scalar-DFG feed dict (frontend.braggnn names, batch index 0)
    into this model's param tree — lets the testbench drive both paths with
    identical weights."""
    f = {k: np.asarray(v)[0] for k, v in feeds.items()}
    out = {
        "conv1": {"w": f["conv1.weight"], "b": f["conv1.bias"]},
        "nlb": {
            "theta": {"w": f["nlb.theta.weight"]},
            "phi": {"w": f["nlb.phi.weight"]},
            "g": {"w": f["nlb.g.weight"]},
            "out": {"w": f["nlb.out_cnn.weight"]},
        },
        "conv2a": {"w": f["cnn2.conv1.weight"], "b": f["cnn2.conv1.bias"]},
        "conv2b": {"w": f["cnn2.conv2.weight"], "b": f["cnn2.conv2.bias"]},
    }
    for li in range(4):
        out[f"dense{li}"] = {"w": f[f"dense.{li}.weight"],
                             "b": f[f"dense.{li}.bias"]}
    return jax.tree_util.tree_map(jnp.asarray, out)


def synthetic_peaks(key: jax.Array, n: int, img: int = 11
                    ) -> tuple[jax.Array, jax.Array]:
    """Gaussian-blob Bragg-peak surrogates + centre labels (for training
    demos and the precision/accuracy study)."""
    k1, k2, k3 = jax.random.split(key, 3)
    centers = jax.random.uniform(k1, (n, 2), minval=3.0, maxval=img - 3.0)
    sigma = jax.random.uniform(k2, (n, 1, 1), minval=0.8, maxval=1.6)
    yy, xx = jnp.mgrid[0:img, 0:img]
    blob = jnp.exp(-(((yy[None] - centers[:, 0, None, None]) ** 2
                      + (xx[None] - centers[:, 1, None, None]) ** 2)
                     / (2 * sigma ** 2)))
    noise = 0.02 * jax.random.normal(k3, blob.shape)
    imgs = (blob + noise)[:, None, :, :].astype(jnp.float32)
    labels = centers / img                      # normalised to [0,1]
    return imgs, labels.astype(jnp.float32)
