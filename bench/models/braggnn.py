"""BraggNN for the benchmark: weights, the plain reference and its costs.

The reference, :func:`forward`, imports nothing of the program; only
:func:`build_module` reaches into it, for the system under test.  The network is BraggNN(s) as the
OpenHLS paper deploys it (arXiv 2302.06751, section 4.2): conv1, a
non-local block whose softmax uses the order-8 Taylor exponential with two
halvings of range, three ReLU convolutions and four ReLU dense layers.
The Taylor softmax is the one departure from the textbook BraggNN, and it
is what the configuration states: the compiled design computes it.

``fmt=(wE, wF)`` gives the FloPoCo datapath the configuration serves: both
operands of every convolution and dense contraction rounded to the
``(wE, wF)`` lattice, and every layer's result rounded again, as the design
does at its kernel boundaries; biases and the softmax stay in float32.
``precision`` is the contraction precision: ``"highest"`` is float32, and
``"high"`` is the three-pass bfloat16 product (each operand split into a
bfloat16 high and low part, the low-by-low product dropped), written out
with integer rounding so that it computes the same on every backend.

:func:`init_params` draws the weights from the seed on the device, in one
jitted call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# -- FloPoCo quantiser (round to nearest even, flush to zero, saturate) ------

def _pow2(e):
    bits = (jnp.clip(e, -126, 127) + 127).astype(jnp.int32) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def quantize(x, fmt):
    """Round ``x`` to the FloPoCo ``(wE, wF)`` value lattice."""
    exp_bits, man_bits = fmt
    bias = (1 << (exp_bits - 1)) - 1
    emin, emax = 1 - bias, bias
    max_value = (2.0 - 2.0 ** (-man_bits)) * 2.0 ** emax
    min_normal = 2.0 ** emin
    x = jnp.asarray(x, jnp.float32)
    sign = jnp.sign(x)
    v = jnp.abs(x)
    f, e = jnp.frexp(v)
    m, e = f * 2.0, e - 1
    scale = float(1 << man_bits)
    q = jnp.round((m - 1.0) * scale)
    carry = q >= scale
    out = sign * jnp.where(carry, 1.0, 1.0 + q / scale) \
        * _pow2(jnp.where(carry, e + 1, e))
    out = jnp.where(v < min_normal * 0.5, 0.0, out)
    out = jnp.where((v >= min_normal * 0.5) & (v < min_normal),
                    sign * min_normal, out)
    out = jnp.where(v > max_value, sign * max_value, out)
    out = jnp.where(v == 0.0, x, out)
    return jnp.where(v <= np.finfo(np.float32).max, out, x)


# -- contractions at a stated precision --------------------------------------

def _bf16_part(x):
    """``x`` rounded to bfloat16 (to nearest, ties to even), in float32.
    Integer arithmetic on the bits, because a backend may skip the rounding
    of a float32 -> bfloat16 -> float32 round trip (XLA's excess precision
    on the TPU)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))
    return jax.lax.bitcast_convert_type(bits & np.uint32(0xFFFF0000),
                                        jnp.float32)


def _split_bf16(x):
    hi = _bf16_part(x)
    return hi, _bf16_part(x - hi)


def _contract(fn, a, b, precision: str):
    """``fn(a, b)``, bilinear, at ``precision`` ('highest' or 'high')."""
    if precision == "highest":
        return fn(a, b, HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    # the partial products are exact in float32; HIGHEST only keeps the
    # backend from rounding their operands again
    return fn(ah, bh, HIGHEST) + (fn(ah, bl, HIGHEST) + fn(al, bh, HIGHEST))


def _conv_fn(x, w, prec):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=prec)


def _taylor_softmax(x, order: int, range_reduce: int):
    z = x - jnp.max(x, axis=-1, keepdims=True)
    y = z / float(1 << range_reduce)
    acc = jnp.ones_like(y)
    term = jnp.ones_like(y)
    for k in range(1, order + 1):
        term = term * y / float(k)
        acc = acc + term
    for _ in range(range_reduce):
        acc = acc * acc
    return acc / jnp.sum(acc, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=(
    "fmt", "precision", "taylor_order", "range_reduce"))
def forward(params, x, *, fmt=None, precision: str = "highest",
            taylor_order: int = 8, range_reduce: int = 2):
    """``x`` (B, 1, img, img), or (B, 1, 1, img, img) -> (B, 2)."""
    q = (lambda a: quantize(a, fmt)) if fmt is not None else (lambda a: a)

    def conv(a, p, bias=True):
        y = _contract(_conv_fn, q(a), q(p["w"]), precision)
        return y + p["b"][None, :, None, None] if bias else y

    def einsum(spec, a, b):
        return _contract(
            lambda u, v, prec: jnp.einsum(spec, u, v, precision=prec),
            a, b, precision)

    x = x.reshape((x.shape[0], 1) + x.shape[-2:]).astype(jnp.float32)
    feat = q(conv(x, params["conv1"]))
    nlb = params["nlb"]
    b, c1, h, w = feat.shape
    theta, phi, g = (q(conv(feat, nlb[k], bias=False))
                     for k in ("theta", "phi", "g"))
    c2 = theta.shape[1]
    tf, pf, gf = (t.reshape(b, c2, h * w) for t in (theta, phi, g))
    scores = q(einsum("bci,bcj->bij", tf, pf))
    attn = _taylor_softmax(scores, taylor_order, range_reduce)
    y = q(einsum("bij,bcj->bci", attn, gf)).reshape(b, c2, h, w)
    z = q(conv(y, nlb["out"], bias=False))
    r = jnp.maximum(q(feat + z), 0.0)
    r = q(jnp.maximum(conv(r, params["conv2a"]), 0.0))
    r = q(jnp.maximum(conv(r, params["conv2b"]), 0.0))
    flat = r.reshape(b, -1)
    for li in range(4):
        d = params[f"dense{li}"]
        y = _contract(lambda u, v, prec: jnp.dot(u, v, precision=prec),
                      q(flat), q(d["w"].T), precision)
        flat = q(jnp.maximum(y + d["b"], 0.0))
    return flat


def forward_blocks(params, x, *, block: int = 4096, **kw) -> np.ndarray:
    """:func:`forward` over ``x`` in blocks of rows, gathered on the host."""
    outs = []
    for i in range(0, len(x), block):
        part = np.asarray(x[i:i + block], np.float32)
        if len(part) < block and len(x) > block:
            # pad the last block, so that every block has the same shape
            pad = np.zeros((block - len(part),) + part.shape[1:], np.float32)
            outs.append(np.asarray(forward(
                params, np.concatenate([part, pad]), **kw))[:len(part)])
        else:
            outs.append(np.asarray(forward(params, part, **kw)))
    return np.concatenate(outs) if outs else np.zeros((0, 2), np.float32)


# -- weights -----------------------------------------------------------------

def shapes(s: int, img: int) -> dict:
    """The parameter tree's shapes, in the layout the model binds."""
    c1, c2, h3 = 16 * s, 8 * s, img - 6
    dims = [2 * s * h3 * h3, 16 * s, 8 * s, 4 * s, 2]
    tree = {
        "conv1": {"w": (c1, 1, 3, 3), "b": (c1,)},
        "nlb": {"theta": {"w": (c2, c1, 1, 1)}, "phi": {"w": (c2, c1, 1, 1)},
                "g": {"w": (c2, c1, 1, 1)}, "out": {"w": (c1, c2, 1, 1)}},
        "conv2a": {"w": (c2, c1, 3, 3), "b": (c2,)},
        "conv2b": {"w": (2 * s, c2, 3, 3), "b": (2 * s,)},
    }
    for li in range(4):
        tree[f"dense{li}"] = {"w": (dims[li + 1], dims[li]),
                              "b": (dims[li + 1],)}
    return tree


def init_params(seed: int, s: int, img: int, *, out_bias: tuple,
                attn_scale: float):
    """Seeded float32 weights on the device, drawn in one jitted call.

    Weights are He-normal (``sqrt(2 / fan_in)``), so activations keep their
    size through the ReLU layers; hidden biases are ``0.1`` times a normal.
    The non-local block's ``theta`` and ``phi`` weights are scaled by
    ``attn_scale`` more, so that on the detector's frames the attention
    scores stay within about 10 of each row's maximum, where the order-8
    Taylor exponential holds (at the plain He scale they spread over
    hundreds, and the exponential overflows to inf).  The last layer's
    bias is uniform in ``out_bias``, so that the final ReLU leaves most
    outputs non-zero.  (A plain random init gives near-zero outputs, and
    the benchmark does not train.)
    """
    hi, lo = (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF
    return _drawer(s, img, tuple(out_bias), float(attn_scale))(
        np.uint32(hi), np.uint32(lo))


@functools.lru_cache(maxsize=None)
def _drawer(s: int, img: int, out_bias: tuple, attn_scale: float):
    """The jitted draw for one shape of network: one normal draw cut into
    every leaf, and one uniform draw for the last bias."""
    is_leaf = lambda t: isinstance(t, tuple)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(s, img), is_leaf=is_leaf)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    leaves = [shp for _, shp in flat]
    sizes = [int(np.prod(shp)) for shp in leaves]
    offsets = np.cumsum([0] + sizes)
    scales = [0.1 if path.endswith("['b']")
              else float(np.sqrt(2.0 / np.prod(shp[1:])))
              * (attn_scale if path.startswith(("['nlb']['theta']",
                                                "['nlb']['phi']")) else 1.0)
              for path, shp in zip(paths, leaves)]

    def draw(hi, lo):
        # the seed may exceed 32 bits: fold it in as two 32-bit words
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), hi),
                                 lo)
        kn, ku = jax.random.split(key)
        z = jax.random.normal(kn, (int(offsets[-1]),), jnp.float32)
        out = []
        for path, shp, o, n, sc in zip(paths, leaves, offsets, sizes, scales):
            if path == "['dense3']['b']":
                out.append(jax.random.uniform(ku, shp, jnp.float32,
                                              *out_bias))
            else:
                out.append(sc * z[o:o + n].reshape(shp))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)


# -- what a configuration file names ------------------------------------------

def make_params(cfg: dict, seed: int):
    w = cfg["weights"]
    return init_params(seed, cfg["s"], cfg["img"],
                       out_bias=tuple(w["out_bias"]),
                       attn_scale=w["attn_scale"])


def build_module(cfg: dict, params):
    """The system under test: the program's BraggNN module, bound."""
    from repro.models import braggnn
    return braggnn.build(cfg["s"], cfg["img"],
                         taylor_order=cfg["taylor_order"]).bind(params)


def input_shape(cfg: dict) -> tuple:
    """One sample's input memref."""
    return (1, 1, cfg["img"], cfg["img"])


def reference(params, x, cfg: dict, *, control: bool = False) -> np.ndarray:
    """The configuration's datapath over ``x``; ``control=True`` computes
    it one precision step lower, as the configuration's ``control`` says."""
    kw = {"fmt": tuple(cfg["fmt"]) if cfg["fmt"] else None,
          "precision": cfg["matmul_precision"],
          "taylor_order": cfg["taylor_order"]}
    if control:
        kw.update({k: tuple(v) if isinstance(v, list) else v
                   for k, v in cfg["control"].items()})
    return forward_blocks(params, x, **kw)


# -- operations and bytes ------------------------------------------------------

def layers(cfg: dict) -> list[dict]:
    """Each contraction of one sample: the kernel that computes it in the
    nest tier (``None`` where XLA does), multiply-adds, and float32 bytes
    of its logical input, weights and output (no im2col patches)."""
    s, img = cfg["s"], cfg["img"]
    c1, c2, h1, h2, h3 = 16 * s, 8 * s, img - 2, img - 4, img - 6
    n = h1 * h1
    out = []

    def conv(name, cin, cout, k, hin, bias):
        hout = hin - k + 1
        out.append({"name": name, "kernel": "conv2d_vmem",
                    "macs": hout * hout * cout * cin * k * k,
                    "act_bytes": 4 * (cin * hin * hin + cout * hout * hout),
                    "weight_bytes": 4 * (cout * cin * k * k
                                         + (cout if bias else 0))})

    conv("conv1", 1, c1, 3, img, True)
    for name in ("theta", "phi", "g"):
        conv(name, c1, c2, 1, h1, False)
    out.append({"name": "scores", "kernel": None, "macs": n * n * c2,
                "act_bytes": 4 * (2 * c2 * n + n * n), "weight_bytes": 0})
    out.append({"name": "softmax", "kernel": "fused_softmax", "macs": 0,
                "act_bytes": 4 * 2 * n * n, "weight_bytes": 0})
    out.append({"name": "mix", "kernel": None, "macs": n * n * c2,
                "act_bytes": 4 * (n * n + 2 * c2 * n), "weight_bytes": 0})
    conv("out", c2, c1, 1, h1, False)
    conv("conv2a", c1, c2, 3, h1, True)
    conv("conv2b", c2, 2 * s, 3, h2, True)
    dims = [2 * s * h3 * h3, 16 * s, 8 * s, 4 * s, 2]
    for li in range(4):
        out.append({"name": f"dense{li}", "kernel": "smallfloat_matmul",
                    "macs": dims[li] * dims[li + 1],
                    "act_bytes": 4 * (dims[li] + dims[li + 1]),
                    "weight_bytes": 4 * (dims[li] + 1) * dims[li + 1]})
    return out


def model_flops(cfg: dict) -> int:
    """FLOPs of one sample: 2 per multiply-add of the convolutions, the
    attention's two contractions and the dense layers.  The Taylor softmax,
    biases and ReLUs are not counted."""
    return 2 * sum(layer["macs"] for layer in layers(cfg))
