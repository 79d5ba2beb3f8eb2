"""Pallas-native emission: the one rendering every design serves through.

The functional simulator (``emit.evaluate``) is the reference; this module
compiles the scheduled design instead of interpreting it, with two tiers:

**Nest-pattern tier** (``mode='nests'``) — when the design carries the
``ModuleGraph`` it was bridged from, each node lowers through the kernel
registry (:mod:`repro.kernels.registry`): ``Conv2d`` -> the
weights-in-VMEM conv exemplar, ``Linear`` -> the smallfloat matmul,
``Softmax`` and the NLB attention softmax -> the fused Taylor softmax,
the NLB attention core optionally -> flash attention.  ReLU nodes fuse
into the preceding conv/matmul kernel.  Nodes without a registered kernel
(batch norm, pooling, strided/padded conv) run on the plain tensor path
and are recorded as fallbacks in the :class:`PallasPlan`.

**Generic DFG tier** (``mode='dfg'``) — works for *any* traced design:
the Kahn-wave levelisation and per-(level, opcode) grouping of
``core/emit.py`` (the right unit of fusion since the struct-of-arrays IR)
is partitioned into contiguous runs of kernel-supported groups, and each
run becomes ONE fused kernel: gather indices baked in as static arrays,
compute vectorised per group, and a group's scatter elided entirely when
its result set is consumed exactly through an aligned gather later in the
same segment (the value is forwarded in-register instead).  Groups whose
opcode has no entry in ``registry.OPCODE_KERNELS`` fall back per-group to
the tensor path and are recorded.  With ``fmt`` every group result is
re-quantised — the per-op FloPoCo functional model, bit-matching
``emit.evaluate``.

``use_pallas`` routes segment bodies / registry kernels through real
``pl.pallas_call`` lowerings.  On the TPU the nest tier's kernels compile
with Mosaic, the TPU kernel compiler; off the TPU they run in the Pallas
interpreter.  That choice is made in one place (:func:`to_pallas_fn`) and
recorded as ``PallasPlan.interpret``.  The default off the TPU is the
kernels' own oracle discipline: same lowering, executed as plain XLA.  The
DFG tier's fused segments gather from the value buffer with dynamic
indices, which Mosaic cannot lower, so on the TPU they run as XLA programs
by rule and ``use_pallas=True`` is refused there.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import numpy as np

from repro import obs
from repro.core import emit
from repro.core.ir import Graph
from repro.core.precision import FORMATS, FloatFormat
from repro.kernels import registry as kreg

#: per-sample flop-free node types the nest tier implements inline without
#: counting them as kernel fallbacks
_TRIVIAL_NODES = ("ReLU", "OutputReLU", "Flatten")


def _on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def _norm_fmt(fmt) -> tuple[Optional[FloatFormat], Optional[str]]:
    """-> (FloatFormat or None, format key or None)."""
    if fmt is None or fmt == "fp32":
        return None, None
    if isinstance(fmt, str):
        return FORMATS[fmt], fmt
    if isinstance(fmt, FloatFormat):
        key = next((k for k, v in FORMATS.items() if v == fmt), None)
        return fmt, key or f"{fmt.exp_bits}_{fmt.man_bits}"
    raise TypeError(f"fmt must be None, a FORMATS key or a FloatFormat, "
                    f"got {type(fmt).__name__}")


@dataclasses.dataclass
class PallasPlan:
    """What the lowering actually did — serving telemetry + test surface."""

    mode: str                                  #: 'nests' | 'dfg'
    use_pallas: bool                           #: real pl.pallas_call bodies?
    interpret: bool                            #: Pallas interpreter (off TPU)?
    fmt: Optional[str] = None                  #: FloPoCo key, None = fp32
    bodies: str = ""                           #: what executes the bodies
    n_groups: int = 0                          #: levelised groups (dfg tier)
    n_segments: int = 0                        #: fused kernels (dfg tier)
    fused_scatters: int = 0                    #: scatter->gather pairs elided
    kernels: dict = dataclasses.field(default_factory=dict)
    #: each ``Linear``'s ``smallfloat_matmul`` blocking, by node name
    blocks: dict = dataclasses.field(default_factory=dict)
    #: each conv's ``conv2d_vmem`` form (``conv_form``), by node name
    convs: dict = dataclasses.field(default_factory=dict)
    fallbacks: list = dataclasses.field(default_factory=list)
    notes: list = dataclasses.field(default_factory=list)

    def record_kernel(self, name: str) -> None:
        self.kernels[name] = self.kernels.get(name, 0) + 1

    def summary(self) -> str:
        kern = ", ".join(f"{k}x{v}" for k, v in sorted(self.kernels.items()))
        parts = [f"pallas[{self.mode}] {self.fmt or 'fp32'}",
                 f"use_pallas={self.use_pallas} interpret={self.interpret}"]
        if self.mode == "dfg":
            parts.append(f"{self.n_segments} fused kernels over "
                         f"{self.n_groups} groups "
                         f"({self.fused_scatters} scatters elided)")
        if kern:
            parts.append(kern)
        parts.append(f"{len(self.fallbacks)} fallbacks")
        parts.append(self.bodies)
        return "; ".join(parts)


# ---------------------------------------------------------------------------
# Generic tier: fuse levelised op groups into compiled kernels
# ---------------------------------------------------------------------------

def _fallback_compute(oc: str, a: list):
    """The tensor-path rendering of one unkernelled group (mirrors
    ``emit.evaluate``'s op table for the opcodes outside the registry)."""
    import jax.numpy as jnp
    if oc == "cmpugt":
        return (a[0] > a[1]).astype(jnp.float32)
    if oc == "select":
        return jnp.where(a[0] > 0.5, a[1], a[2])
    table = kreg.OPCODE_KERNELS
    if oc in table:
        return table[oc][1](a)
    raise NotImplementedError(oc)  # pragma: no cover


def _plan_segments(groups, output_vids: np.ndarray, opcode_table,
                   plan: PallasPlan):
    """Partition the level-ordered groups into fused segments + fallbacks.

    Returns ``steps``: a list of ``('segment', [(oc, arg_idx, res_idx,
    forward_keys, skip_scatter), ...])`` and ``('fallback', (oc, arg_idx,
    res_idx))`` entries, plus per-group scatter-elision already resolved.
    """
    # consumer bookkeeping: how often each value id is read by later groups,
    # and through which (group, arg-position) gathers
    n_groups = len(groups)
    refs: dict[int, int] = {}
    for _lv, _oc, arg_idx, _res in groups:
        for ai in arg_idx:
            for v in ai:
                refs[int(v)] = refs.get(int(v), 0) + 1
    out_set = set(int(v) for v in output_vids)

    raw_steps: list[tuple[str, Any]] = []
    cur: list[int] = []          # group indices of the open segment
    for gi, (lv, oc, arg_idx, res_idx) in enumerate(groups):
        if oc in opcode_table:
            cur.append(gi)
        else:
            if cur:
                raw_steps.append(("segment", cur))
                cur = []
            raw_steps.append(("fallback", gi))
            plan.fallbacks.append(f"L{lv}:{oc} ({len(res_idx)} ops)")
    if cur:
        raw_steps.append(("segment", cur))

    # scatter elision: a group's scatter is dropped iff its results are not
    # design outputs and every read of them happens through a later gather
    # *in the same segment* whose index array matches bit-for-bit (those
    # gathers are then served from the forwarded register value).
    steps = []
    for kind, payload in raw_steps:
        if kind == "fallback":
            lv, oc, arg_idx, res_idx = groups[payload]
            steps.append(("fallback", (oc, arg_idx, res_idx)))
            continue
        seg_groups = payload
        produced: dict[bytes, int] = {}      # res bytes -> group position
        matched_reads: dict[int, int] = {}   # producer pos -> forwarded reads
        gathers = []                         # per group: arg keys
        for pos, gi in enumerate(seg_groups):
            _lv, oc, arg_idx, res_idx = groups[gi]
            keys = []
            for ai in arg_idx:
                k = ai.tobytes()
                keys.append(k if k in produced else None)
                if k in produced:
                    matched_reads[produced[k]] = \
                        matched_reads.get(produced[k], 0) + len(ai)
            gathers.append(keys)
            produced[res_idx.tobytes()] = pos
        seg = []
        for pos, gi in enumerate(seg_groups):
            _lv, oc, arg_idx, res_idx = groups[gi]
            valid = res_idx >= 0
            total_reads = sum(refs.get(int(v), 0) for v in res_idx[valid])
            is_output = any(int(v) in out_set for v in res_idx[valid])
            skip = (valid.all() and not is_output
                    and matched_reads.get(pos, 0) == total_reads
                    and total_reads > 0)
            if skip:
                plan.fused_scatters += 1
            seg.append((oc, arg_idx, res_idx, gathers[pos], skip))
        steps.append(("segment", seg))
    plan.n_segments = sum(1 for k, _ in steps if k == "segment")
    return steps


def _segment_body(seg, opcode_table, q, n_values: int):
    """One fused segment -> ``(body(buf, idx) -> buf, idx_flat)``.

    The body is shared verbatim between the ``pl.pallas_call`` kernel and
    the oracle (plain XLA) execution — the lowering is identical, only the
    launch differs.  All gather/scatter index arrays of the segment are
    concatenated into ONE static int32 vector (``idx_flat``) addressed by
    compile-time offsets, because a Pallas kernel cannot capture array
    constants — the index vector rides along as a kernel input instead.
    Result slots of ops without a destination are redirected one past the
    buffer and dropped by the scatter.
    """
    layout = []
    chunks: list[np.ndarray] = []
    off = 0
    for (oc, arg_idx, res_idx, keys, skip) in seg:
        spans = []
        for ai in arg_idx:
            spans.append((off, len(ai)))
            chunks.append(ai.astype(np.int32))
            off += len(ai)
        res_full = np.where(res_idx >= 0, res_idx, n_values)
        rspan = (off, len(res_full))
        chunks.append(res_full.astype(np.int32))
        off += len(res_full)
        layout.append((oc, keys, skip, spans, rspan, res_idx.tobytes()))
    idx_flat = (np.concatenate(chunks) if chunks
                else np.zeros(1, np.int32))

    def body(buf, idx):
        fwd: dict[bytes, Any] = {}
        for oc, keys, skip, spans, (ro, rl), rkey in layout:
            a = [fwd[k] if k is not None and k in fwd
                 else buf[:, idx[o:o + l]]
                 for k, (o, l) in zip(keys, spans)]
            r = opcode_table[oc][1](a)
            if q is not None and oc not in kreg.NO_QUANT_OPCODES:
                r = q(r)
            fwd[rkey] = r
            if not skip:
                buf = buf.at[:, idx[ro:ro + rl]].set(r, mode="drop")
        return buf

    return body, idx_flat


def _segment_fn(body, idx_flat: np.ndarray, use_pallas: bool,
                interpret: bool):
    """Launch one fused segment: real ``pl.pallas_call`` or oracle body."""
    import jax.numpy as jnp

    jidx = jnp.asarray(idx_flat)
    if not use_pallas:
        return lambda buf: body(buf, jidx)
    import jax
    from jax.experimental import pallas as pl

    ni = len(idx_flat)

    def kernel(b_ref, i_ref, o_ref):
        o_ref[...] = body(b_ref[...], i_ref[...])

    def launch(buf):
        batch, nv = buf.shape
        bb = 8 if batch % 8 == 0 else 1
        # one grid step owns a block of samples; the whole value buffer is
        # VMEM-resident for the segment's lifetime (the no-BRAM discipline)
        return pl.pallas_call(
            kernel,
            grid=(batch // bb,),
            in_specs=[pl.BlockSpec((bb, nv), lambda i: (i, 0)),
                      pl.BlockSpec((ni,), lambda i: (0,))],
            out_specs=pl.BlockSpec((bb, nv), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((batch, nv), jnp.float32),
            interpret=interpret,
        )(buf, jidx)

    return launch


def _lower_dfg(g: Graph, *, fmt_obj, use_pallas: bool, interpret: bool,
               opcode_table, plan: PallasPlan):
    import jax.numpy as jnp
    from repro.core.precision import quantize

    c = g.cols()
    groups = emit.compile_groups(c, g.n_values)
    plan.n_groups = len(groups)
    const_idx, const_val, input_scatter, output_gather = emit.io_tables(g)
    all_out_vids = (np.concatenate([v for v, _ in output_gather.values()])
                    if output_gather else np.zeros(0, np.int32))
    q = (lambda x: quantize(x, fmt_obj)) if fmt_obj is not None else None
    steps = _plan_segments(groups, all_out_vids, opcode_table, plan)

    n_values = g.n_values
    compiled = []
    for kind, payload in steps:
        if kind == "segment":
            body, idx_flat = _segment_body(payload, opcode_table, q,
                                           n_values)
            compiled.append(_segment_fn(body, idx_flat, use_pallas,
                                        interpret))
        else:
            oc, arg_idx, res_idx = payload
            jargs = [jnp.asarray(ai) for ai in arg_idx]
            jres = jnp.asarray(np.where(res_idx >= 0, res_idx,
                                        n_values).astype(np.int32))

            def fb(buf, oc=oc, jargs=jargs, jres=jres):
                r = _fallback_compute(oc, [buf[:, ja] for ja in jargs])
                if q is not None and oc not in kreg.NO_QUANT_OPCODES:
                    r = q(r)
                return buf.at[:, jres].set(r, mode="drop")

            compiled.append(fb)
    input_rank = {name: len(next(iter(g.inputs[name])))
                  for name in input_scatter}
    cval = q(jnp.asarray(const_val)) if q is not None \
        else jnp.asarray(const_val)

    def _prologue(feeds):
        batch = 1
        for name in input_scatter:
            shp = jnp.shape(feeds[name])
            if len(shp) == input_rank[name] + 1:
                batch = shp[0]
                break
        buf = jnp.zeros((batch, n_values), dtype=jnp.float32)
        buf = buf.at[:, const_idx].set(cval[None, :])
        for name, (vids, idxs) in input_scatter.items():
            arr = jnp.asarray(feeds[name], dtype=jnp.float32)
            if arr.ndim == len(idxs[0]):
                arr = arr[None]
            flat = jnp.stack([arr[(slice(None),) + i] for i in idxs], axis=1)
            if q is not None:
                flat = q(flat)
            buf = buf.at[:, vids].set(flat)
        return buf, batch

    def _epilogue(buf, batch):
        return {name: buf[:, vids].reshape((batch,) + shape)
                for name, (vids, shape) in output_gather.items()}

    def run(feeds):
        buf, batch = _prologue(feeds)
        for step in compiled:
            buf = step(buf)
        return _epilogue(buf, batch)

    return run


# ---------------------------------------------------------------------------
# Nest-pattern tier: registry kernels per bridged module node
# ---------------------------------------------------------------------------

def _lower_module(module, *, fmt_obj, fmt_tuple, use_pallas: bool,
                  interpret: bool, nlb_flash: bool, plan: PallasPlan):
    import jax.numpy as jnp
    from jax import lax
    from repro.core.precision import quantize
    from repro.kernels.conv2d_vmem.conv2d_vmem import conv_form
    from repro.kernels.smallfloat_matmul.smallfloat_matmul import (
        blocking as mm_blocking)
    from repro.nn import graph as nng

    if module.input_shape[0] != 1 and len(module.input_shape) != 2:
        raise ValueError(
            f"nest tier expects a per-sample memref input shape with a "
            f"leading 1 (image models) or a 2-D (L, D) sequence shape, "
            f"got {module.input_shape}; use mode='dfg'")

    conv_e = kreg.for_pattern("Conv2d")
    mm_e = kreg.for_pattern("Linear")
    sm_e = kreg.for_pattern("Softmax")
    fa_e = kreg.for_pattern("NonLocalBlock.attention")
    kw = {"use_pallas": use_pallas, "interpret": interpret}
    q = (lambda x: quantize(x, fmt_obj)) if fmt_obj is not None \
        else (lambda x: x)

    nodes = list(module.nodes)
    in_shapes = [tuple(module.input_shape)] + module.shapes()[:-1]
    weight_names: list[str] = []
    for n in nodes:
        weight_names.extend(n.weight_memrefs())

    steps: list[Callable] = []   # each: (x, w: dict) -> x
    i = 0
    while i < len(nodes):
        node = nodes[i]
        fuse_relu = (i + 1 < len(nodes)
                     and isinstance(nodes[i + 1],
                                    (nng.ReLU, nng.OutputReLU)))
        if isinstance(node, nng.Conv2d):
            wn, bn = f"{node.prefix}.weight", f"{node.prefix}.bias"
            has_b = node.bias
            if node.stride == 1 and node.padding == 0:
                plan.record_kernel(conv_e.name + (":relu" if fuse_relu
                                                 else ""))
                _, cin, h, wd = in_shapes[i]
                plan.convs[node.name] = conv_form(
                    cin, h, wd, node.out_channels, node.kernel, node.kernel)

                def step(x, w, wn=wn, bn=bn, has_b=has_b, fr=fuse_relu):
                    return q(conv_e.fn(x, w[wn], w[bn] if has_b else None,
                                       fmt=fmt_tuple, fuse_relu=fr, **kw))
            else:
                plan.fallbacks.append(
                    f"{node.name}: Conv2d(stride={node.stride}, "
                    f"padding={node.padding}) via jnp")

                def step(x, w, wn=wn, bn=bn, has_b=has_b, fr=fuse_relu,
                         node=node):
                    xq, wq = x, w[wn]
                    if fmt_obj is not None:
                        xq, wq = q(xq), q(wq)
                    p = node.padding
                    y = lax.conv_general_dilated(
                        xq, wq, window_strides=(node.stride,) * 2,
                        padding=[(p, p), (p, p)],
                        dimension_numbers=("NCHW", "OIHW", "NCHW"))
                    if has_b:
                        y = y + w[bn][None, :, None, None]
                    if fr:
                        y = jnp.maximum(y, 0.0)
                    return q(y)
        elif isinstance(node, nng.Linear):
            wn, bn = f"{node.prefix}.weight", f"{node.prefix}.bias"
            has_b = node.bias
            eb = fmt_obj.exp_bits if fmt_obj is not None else None
            mb = fmt_obj.man_bits if fmt_obj is not None else None
            plan.record_kernel(mm_e.name + (":relu" if fuse_relu else ""))
            plan.blocks[node.name] = mm_blocking(node.in_features,
                                                 node.out_features)

            def step(x, w, wn=wn, bn=bn, has_b=has_b, fr=fuse_relu,
                     eb=eb, mb=mb):
                # loop-nest semantics: out = x @ W.T + b
                return q(mm_e.fn(x, w[wn].T, w[bn] if has_b else None,
                                 exp_bits=eb, man_bits=mb, fuse_relu=fr,
                                 **kw))
        elif isinstance(node, nng.Softmax):
            plan.record_kernel(sm_e.name)

            def step(x, w, node=node, fr=fuse_relu):
                y = sm_e.fn(x, taylor_order=node.taylor_order, **kw)
                return jnp.maximum(y, 0.0) if fr else y
        elif isinstance(node, nng.NonLocalBlock):
            _, c1, h, wd = in_shapes[i]
            c2 = node.mid_channels
            for sub, cin, cout in (("theta", c1, c2), ("phi", c1, c2),
                                   ("g", c1, c2), ("out_cnn", c2, c1)):
                plan.convs[f"{node.name}.{sub}"] = conv_form(
                    cin, h, wd, cout, 1, 1)
            steps.append(_nlb_step(node, conv_e, sm_e, fa_e, q, fmt_tuple,
                                   kw, nlb_flash, plan))
            fuse_relu = False
            i += 1
            continue
        elif isinstance(node, nng.BatchNorm2d):
            plan.fallbacks.append(f"{node.name}: BatchNorm2d via jnp")
            pre = node.prefix

            def step(x, w, pre=pre, node=node, fr=fuse_relu):
                ga, be = w[f"{pre}.gamma"], w[f"{pre}.beta"]
                mu, va = w[f"{pre}.mean"], w[f"{pre}.var"]
                if fmt_obj is not None:
                    x, ga, be = q(x), q(ga), q(be)
                    mu, va = q(mu), q(va)
                den = jnp.sqrt(va + node.eps)
                y = ga[None, :, None, None] \
                    * (x - mu[None, :, None, None]) \
                    / den[None, :, None, None] + be[None, :, None, None]
                if fr:
                    y = jnp.maximum(y, 0.0)
                return q(y)
        elif isinstance(node, nng.MaxPool2d):
            plan.fallbacks.append(f"{node.label}: MaxPool2d via "
                                  f"reduce_window")

            def step(x, w, node=node, fr=fuse_relu):
                y = lax.reduce_window(
                    x, -jnp.inf, lax.max,
                    (1, 1, node.kernel, node.kernel),
                    (1, 1, node.stride, node.stride), "VALID")
                return jnp.maximum(y, 0.0) if fr else y
        elif isinstance(node, nng.RMSNorm):
            plan.fallbacks.append(f"{node.name}: RMSNorm via jnp")
            pre = node.prefix

            def step(x, w, pre=pre, node=node):
                ga = w[f"{pre}.gamma"]
                if fmt_obj is not None:
                    x, ga = q(x), q(ga)
                ms = jnp.sum(x * x, axis=-1, keepdims=True) \
                    * (1.0 / x.shape[-1])
                return q(x * (1.0 / jnp.sqrt(ms + node.eps)) * ga)
            fuse_relu = False
        elif isinstance(node, nng.Attention):
            steps.append(_attention_step(node, mm_e, sm_e, fa_e, q,
                                         fmt_obj, fmt_tuple, kw, nlb_flash,
                                         plan))
            fuse_relu = False
            i += 1
            continue
        elif isinstance(node, nng.MLP):
            steps.append(_mlp_step(node, mm_e, q, fmt_obj, plan, kw))
            fuse_relu = False
            i += 1
            continue
        elif isinstance(node, (nng.ReLU, nng.OutputReLU)):
            def step(x, w):
                return jnp.maximum(x, 0.0)
            fuse_relu = False
        elif isinstance(node, nng.Flatten):
            def step(x, w):
                return x.reshape(x.shape[0], -1)
            fuse_relu = False
        else:  # pragma: no cover - ModuleGraph validates the vocabulary
            raise NotImplementedError(type(node).__name__)
        steps.append(step)
        i += 2 if fuse_relu else 1

    # the output memref is the last allocating node's (OutputReLU rewrites
    # it in place) — mirror hls.bridge.emit_module
    last_alloc = max(j for j, n in enumerate(nodes)
                     if not isinstance(n, nng.OutputReLU))
    out_name = nodes[last_alloc].out_name
    out_shape = module.shapes()[-1]

    def run(x, weights):
        # runs only while jax.jit traces it: a retrace shows by name
        with obs.span("nest.trace", cat="pallas"):
            for step in steps:
                x = step(x, weights)
            return {out_name: x.reshape((x.shape[0],) + tuple(out_shape))}

    return run, weight_names, out_name


def _einsum(spec, a, b):
    """An fp32 contraction outside the kernels, at full fp32 precision
    (the TPU's default f32 matmul rounds operands to bf16)."""
    import jax.numpy as jnp
    from jax import lax
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _nlb_step(node, conv_e, sm_e, fa_e, q, fmt_tuple, kw, nlb_flash: bool,
              plan: PallasPlan):
    """The NonLocalBlock composite: three 1x1 convs -> attention ->
    out-projection -> residual, every stage through a registry kernel."""
    import jax.numpy as jnp

    pre = node.prefix
    use_flash = nlb_flash and fmt_tuple is None
    plan.record_kernel(conv_e.name)          # theta/phi/g (batched 1x1)
    if use_flash:
        plan.record_kernel(fa_e.name)
        plan.notes.append(
            f"{node.name}: flash-attention throughput mode — true-exp "
            f"softmax, not the order-{node.taylor_order} Taylor model")
    else:
        plan.record_kernel(sm_e.name)

    def step(x, w):
        b, c1, h, _ = x.shape
        n = h * h
        theta = q(conv_e.fn(x, w[f"{pre}.theta.weight"], None,
                            fmt=fmt_tuple, **kw))
        phi = q(conv_e.fn(x, w[f"{pre}.phi.weight"], None,
                          fmt=fmt_tuple, **kw))
        g = q(conv_e.fn(x, w[f"{pre}.g.weight"], None,
                        fmt=fmt_tuple, **kw))
        c2 = theta.shape[1]
        tf = theta.reshape(b, c2, n)
        pf = phi.reshape(b, c2, n)
        gf = g.reshape(b, c2, n)
        if use_flash:
            # A = softmax(theta^T phi) — flash divides logits by sqrt(D),
            # so pre-scale q to keep the DFG's unscaled scores
            qv = (tf * jnp.sqrt(jnp.float32(c2))).transpose(0, 2, 1)
            kv = pf.transpose(0, 2, 1)
            vv = gf.transpose(0, 2, 1)
            y = fa_e.fn(qv[:, :, None, :], kv[:, :, None, :],
                        vv[:, :, None, :], causal=False, **kw)
            yc = q(y[:, :, 0, :].transpose(0, 2, 1))         # (B, c2, n)
        else:
            scores = q(_einsum("bci,bcj->bij", tf, pf))
            attn = sm_e.fn(scores, taylor_order=node.taylor_order, **kw)
            yc = q(_einsum("bij,bcj->bci", attn, gf))
        y4 = yc.reshape(b, c2, h, h)
        z = q(conv_e.fn(y4, w[f"{pre}.out_cnn.weight"], None,
                        fmt=fmt_tuple, **kw))
        return q(x + z)

    return step


def _rms_jnp(x, gamma, eps, q):
    import jax.numpy as jnp
    ms = jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / x.shape[-1])
    return q(x * (1.0 / jnp.sqrt(ms + eps)) * gamma)


def _attention_step(node, mm_e, sm_e, fa_e, q, fmt_obj, fmt_tuple, kw,
                    flash: bool, plan: PallasPlan):
    """The Attention composite: optional pre-norm -> q/k/v projections
    (matmul kernel) -> scaled scores -> softmax (Taylor kernel, or flash
    attention in throughput mode) -> mix -> out-projection -> residual."""
    import jax.numpy as jnp

    pre = node.prefix
    h, dh = node.n_heads, node.head_dim
    eb = fmt_obj.exp_bits if fmt_obj is not None else None
    mb = fmt_obj.man_bits if fmt_obj is not None else None
    use_flash = flash and fmt_tuple is None
    plan.record_kernel(mm_e.name)            # q/k/v and out projections
    if use_flash:
        plan.record_kernel(fa_e.name)
        plan.notes.append(
            f"{node.name}: flash-attention throughput mode — true-exp "
            f"softmax, not the order-{node.taylor_order} Taylor model")
    else:
        plan.record_kernel(sm_e.name)

    def step(x, w):
        b, l, d = x.shape
        src = x
        if node.pre_norm:
            ga = w[f"{pre}.norm.gamma"]
            if fmt_obj is not None:
                src, ga = q(src), q(ga)
            src = _rms_jnp(src, ga, node.eps, q)
        x2 = src.reshape(b * l, d)

        def proj(nm):                        # (B*L, D) @ (D, H*dh)
            wk_ = w[f"{pre}.{nm}.kernel"].reshape(d, h * dh)
            y = mm_e.fn(x2, wk_, None, exp_bits=eb, man_bits=mb, **kw)
            return q(y).reshape(b, l, h, dh)

        qh, kh, vh = proj("q"), proj("k"), proj("v")
        if use_flash:
            # flash divides logits by sqrt(dh) — exactly the DFG's scale
            y = fa_e.fn(qh, kh, vh, causal=False, **kw)
        else:
            scores = q(_einsum("bshk,bthk->bhst", qh, kh)
                       * (1.0 / jnp.sqrt(jnp.float32(dh))))
            attn = sm_e.fn(scores, taylor_order=node.taylor_order, **kw)
            y = q(_einsum("bhst,bthk->bshk", attn, vh))
        wo = w[f"{pre}.o.kernel"].reshape(h * dh, d)
        z = q(mm_e.fn(y.reshape(b * l, h * dh), wo, None,
                      exp_bits=eb, man_bits=mb, **kw)).reshape(b, l, d)
        return q(x + z) if node.residual else z

    return step


def _mlp_step(node, mm_e, q, fmt_obj, plan: PallasPlan, kw):
    """The MLP composite: optional pre-norm -> fc1+ReLU -> fc2 -> residual,
    both matmuls through the smallfloat kernel (ReLU fused into fc1)."""
    pre = node.prefix
    eb = fmt_obj.exp_bits if fmt_obj is not None else None
    mb = fmt_obj.man_bits if fmt_obj is not None else None
    plan.record_kernel(mm_e.name + ":relu")  # fc1
    plan.record_kernel(mm_e.name)            # fc2

    def step(x, w):
        b, l, d = x.shape
        src = x
        if node.pre_norm:
            ga = w[f"{pre}.norm.gamma"]
            if fmt_obj is not None:
                src, ga = q(src), q(ga)
            src = _rms_jnp(src, ga, node.eps, q)
        x2 = src.reshape(b * l, d)
        h1 = q(mm_e.fn(x2, w[f"{pre}.fc1.weight"].T,
                       w[f"{pre}.fc1.bias"], exp_bits=eb, man_bits=mb,
                       fuse_relu=True, **kw))
        z = q(mm_e.fn(h1, w[f"{pre}.fc2.weight"].T,
                      w[f"{pre}.fc2.bias"], exp_bits=eb, man_bits=mb,
                      **kw)).reshape(b, l, d)
        return q(x + z) if node.residual else z

    return step


# ---------------------------------------------------------------------------
# Front door
# ---------------------------------------------------------------------------

#: why the DFG tier cannot run ``pl.pallas_call`` bodies on the TPU
_NO_MOSAIC_GATHER = (
    "the DFG tier's fused segments gather from the value buffer with "
    "dynamic indices inside the kernel (buf[:, idx]), and Mosaic, the TPU "
    "kernel compiler, has no lowering for that gather")


def to_pallas_fn(g: Graph, *, module=None, fmt=None, mode: str = "auto",
                 use_pallas: Optional[bool] = None, nlb_flash: bool = False,
                 opcode_table=None) -> Callable:
    """Compile a DFG (plus optional source ``ModuleGraph``) to a callable.

    The returned callable maps a feed dict (memref name -> array, weights
    batched or not) to ``{output name: (batch,) + shape}`` exactly like
    ``emit.evaluate``, is internally jitted (do NOT wrap it in
    ``jax.jit`` — the nest tier normalises fed weights host-side), and
    carries its :class:`PallasPlan` as ``.plan``.  In the nest tier a
    bound module's weights are normalised and put on the device once, at
    lowering; a call then copies only its input, and a weight present in
    the feeds takes the fed array's place for that call.

    ``mode='auto'`` picks the nest-pattern tier when ``module`` is given,
    else the generic DFG tier.  ``fmt`` (a FloPoCo key or ``FloatFormat``)
    quantises: per-op in the DFG tier (the functional model), per-kernel
    operand/result in the nest tier.  ``use_pallas=None`` routes the nest
    tier through real ``pl.pallas_call`` bodies on the TPU (compiled by
    Mosaic) and runs the oracle bodies elsewhere; ``True`` off the TPU
    runs the Pallas lowering in the interpreter.  The DFG tier runs XLA
    segment bodies on the TPU, and ``use_pallas=True`` there raises.
    ``opcode_table`` overrides the DFG tier's opcode registry (tests use
    this to force per-group fallbacks).
    """
    import jax

    fmt_obj, fmt_key = _norm_fmt(fmt)
    if mode == "auto":
        mode = "nests" if module is not None else "dfg"
    if mode not in ("nests", "dfg"):
        raise ValueError(f"unknown pallas lowering mode {mode!r} "
                         f"(valid: auto, nests, dfg)")
    on_tpu = _on_tpu()
    if use_pallas is None:
        use_pallas = on_tpu and mode == "nests"
    if use_pallas and on_tpu and mode == "dfg":
        raise NotImplementedError(
            f"use_pallas=True on the TPU: {_NO_MOSAIC_GATHER}; the DFG tier "
            f"runs XLA segment bodies there (leave use_pallas unset), and "
            f"the nest tier (mode='nests') is the Pallas path on the chip")
    # the one place interpret mode is chosen: only off the TPU
    interpret = bool(use_pallas) and not on_tpu
    if use_pallas:
        bodies = ("Pallas interpreter (off TPU)" if interpret
                  else "Mosaic kernels")
    elif on_tpu:
        bodies = f"XLA segment bodies ({_NO_MOSAIC_GATHER})"
    else:
        bodies = "oracle bodies (off TPU)"
    plan = PallasPlan(mode=mode, use_pallas=bool(use_pallas),
                      interpret=interpret, fmt=fmt_key, bodies=bodies)

    if mode == "nests":
        if module is None:
            raise ValueError("mode='nests' needs the source ModuleGraph "
                             "(compile through repro.hls with an nn model, "
                             "or use mode='dfg')")
        fmt_tuple = (fmt_obj.exp_bits, fmt_obj.man_bits) \
            if fmt_obj is not None else None
        with obs.span("emit.pallas", cat="pallas", mode=mode,
                      fmt=fmt_key) as sp:
            core, weight_names, _ = _lower_module(
                module, fmt_obj=fmt_obj, fmt_tuple=fmt_tuple,
                use_pallas=use_pallas, interpret=interpret,
                nlb_flash=nlb_flash, plan=plan)
            sp.set(kernels=sum(plan.kernels.values()),
                   fallbacks=len(plan.fallbacks))
        _plan_metrics(plan)
        jcore = jax.jit(core)
        in_name = module.input_name
        in_shape = tuple(module.input_shape)
        rank = len(in_shape)

        # the bound weights, normalised and on the device for the life of
        # this callable (no module-level cache: they go with the design)
        resident = {}
        if module.params is not None:
            resident = jax.device_put(_normalize_weights(
                module.weight_feeds(), module))
            obs.inc("nest.weight_uploads")

        def run(feeds):
            fed = [n for n in weight_names if n in feeds]
            missing = [n for n in weight_names
                       if n not in feeds and n not in resident]
            if missing:
                raise KeyError(f"missing weight feeds {missing}")
            x = np.asarray(feeds[in_name], dtype=np.float32)
            if x.ndim == rank:                    # unbatched sample
                x = x[None]
            if in_shape[0] == 1:
                # collapse the loop-nest's per-sample singleton batch axis
                x = x.reshape((x.shape[0],) + in_shape[1:])
            if fed:
                obs.inc("nest.calls_fed")
                with obs.span("nest.weights", cat="pallas"):
                    w = {name: np.asarray(feeds[name], dtype=np.float32)
                         for name in fed}
                    wn = {**resident, **_normalize_weights(w, module)}
            else:
                obs.inc("nest.calls_resident")
                wn = resident
            # dispatch, and the host-to-device copy of the input (and of
            # any fed weights)
            with obs.span("nest.launch", cat="pallas"):
                return dict(jcore(x, wn))

        run.plan = plan
        return run

    with obs.span("emit.pallas", cat="pallas", mode=mode, fmt=fmt_key) as sp:
        core = _lower_dfg(g, fmt_obj=fmt_obj, use_pallas=use_pallas,
                          interpret=interpret,
                          opcode_table=opcode_table or kreg.OPCODE_KERNELS,
                          plan=plan)
        sp.set(segments=plan.n_segments, groups=plan.n_groups,
               fused_scatters=plan.fused_scatters,
               fallbacks=len(plan.fallbacks))
    _plan_metrics(plan)
    run = jax.jit(core)
    run.plan = plan
    return run


def _plan_metrics(plan: PallasPlan) -> None:
    """Lift the lowering plan's counts into the process metrics."""
    obs.inc("pallas.lowerings")
    obs.inc("pallas.segments", plan.n_segments)
    obs.inc("pallas.groups", plan.n_groups)
    obs.inc("pallas.scatter_elisions", plan.fused_scatters)
    obs.inc("pallas.fallbacks", len(plan.fallbacks))
    for kname, n in plan.kernels.items():
        obs.inc(f"pallas.kernel.{kname}", n)
    for blk in plan.blocks.values():
        obs.inc(f"pallas.kernel.smallfloat_matmul:{blk.tag}")
    for form in plan.convs.values():
        obs.inc(f"pallas.kernel.conv2d_vmem:{form}")


def _normalize_weights(w: dict[str, np.ndarray], module) -> dict:
    """Unbatch weight feeds (the nest tier shares one weight set across the
    batch, like the tensor path).  A *varying* batched weight feed cannot
    be expressed as shared kernel weights — fail loudly toward mode='dfg'.
    """
    out = {}
    shapes = {}
    for n in module.nodes:
        sub = n.param_specs()
        if sub is None:
            continue
        for memref, path in n.weight_memrefs().items():
            leaf = sub
            for k in path:
                leaf = leaf[k]
            shapes[memref] = tuple(leaf.shape)
    for name, arr in w.items():
        want = shapes.get(name)
        if want is not None and arr.ndim == len(want) + 1:
            if arr.shape[0] > 1 and not np.all(arr == arr[0]):
                raise ValueError(
                    f"weight feed {name!r} varies across the batch; the "
                    f"nest-pattern tier shares one weight set — use "
                    f"mode='dfg' for per-sample weights")
            arr = arr[0]
        out[name] = arr
    return out
