"""From a traced run to numbers: device busy time, kernels, host spans.

A traced run records a JAX profiler trace of its window.  :func:`load`
keeps three things of it, all on the trace's one clock (ns):

* the device's operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, each event named by its HLO instruction (``conv2d_vmem.12``), its
  result type, and whether it is a Pallas kernel (``tpu_custom_call``);
* the host's events on the threads that ran benchmark code (those with a
  ``bench.*`` annotation): the annotations and the runtime's events;
* the window, the benchmark's ``bench.window`` annotation.

:class:`Trace` reduces them; the readers in ``bench/metrics`` call it.  A
trace can be saved as JSON (:meth:`Trace.to_json`), which is how the tests
keep a small one recorded on the chip.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import json
import re
from pathlib import Path
from typing import Optional

import numpy as np

_HLO = re.compile(r"^%?([\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])")


def options():
    """Profiler options of a traced run: host events, no Python tracer
    (which would slow every Python call of the program)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def annotate_calls(design) -> None:
    """Wrap every Pallas callable this design hands out in a
    ``bench.call`` annotation, so that the trace shows each call's host
    time.  Used by traced runs only."""
    import jax
    jax_fn = design.jax_fn

    def annotated_jax_fn(*args, **kw):
        fn = jax_fn(*args, **kw)

        def call(feeds):
            with jax.profiler.TraceAnnotation("bench.call"):
                return fn(feeds)

        call.plan = getattr(fn, "plan", None)
        return call

    design.jax_fn = annotated_jax_fn


def obs_spans(tracer, t0: float, t1: float) -> list:
    """``[(name, seconds)]`` of the program's spans inside ``[t0, t1]``
    (``time.monotonic``)."""
    return [(s.name, s.t1 - s.t0) for s in tracer.spans()
            if s.t0 >= t0 and s.t1 <= t1]


def _op(text: str) -> tuple[str, str]:
    """(instruction name, result type without layout) of an HLO event."""
    m = _HLO.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), ""
    return m.group(1), m.group(2).lstrip("(")


@dataclasses.dataclass
class Trace:
    ops: list            #: [(name, type, is_kernel, start_ns, dur_ns)]
    host: list           #: [(name, start_ns, dur_ns, thread)]
    window: tuple        #: (start_ns, end_ns) of ``bench.window``

    # -- loading ---------------------------------------------------------

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops, lines = [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for e in line.events:
                        name, typ = _op(e.name)
                        ops.append((name, typ,
                                    "tpu_custom_call" in e.name,
                                    float(e.start_ns), float(e.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = [(e.name, float(e.start_ns), float(e.duration_ns),
                            len(lines)) for e in line.events]
                    if any(h[0].startswith("bench.") for h in evs):
                        lines.append(evs)
        host = [h for evs in lines for h in evs]
        window = next(((h[1], h[1] + h[2]) for h in host
                       if h[0] == "bench.window"), None)
        if window is None:
            raise ValueError("the trace holds no bench.window annotation")
        ws, we = window
        host = [h for h in host if h[1] + h[2] >= ws and h[1] <= we]
        ops = [o for o in ops if o[3] + o[4] >= ws and o[3] <= we]
        return cls(ops=ops, host=host, window=window)

    def to_json(self) -> dict:
        return {"ops": self.ops, "host": self.host,
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops=[tuple(o) for o in d["ops"]],
                   host=[tuple(h) for h in d["host"]],
                   window=tuple(d["window"]))

    # -- device ----------------------------------------------------------

    @functools.cached_property
    def _busy(self) -> np.ndarray:
        """The union of the device's operation intervals in the window, as
        sorted disjoint ``[start, end]`` rows."""
        ws, we = self.window
        iv = sorted((max(o[3], ws), min(o[3] + o[4], we)) for o in self.ops)
        out: list = []
        for s, e in iv:
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return np.asarray(out, dtype=np.float64).reshape(-1, 2)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        b = self._busy
        return float((b[:, 1] - b[:, 0]).sum()) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def busy_within(self, start: float, end: float) -> float:
        """Device-busy seconds inside ``[start, end]`` (ns)."""
        b = self._busy
        lo, hi = np.maximum(b[:, 0], start), np.minimum(b[:, 1], end)
        return float(np.clip(hi - lo, 0, None).sum()) * 1e-9

    def kernel_events(self, kernel: str) -> list:
        """The Pallas kernel's events: instructions named ``kernel`` or
        ``kernel.<n>`` that are TPU custom calls."""
        return [o for o in self.ops if o[2]
                and re.fullmatch(re.escape(kernel) + r"(\.\d+)?", o[0])]

    # -- host ------------------------------------------------------------

    def spans(self, name: str) -> list:
        return [h for h in self.host if h[0] == name]

    def host_activity(self, t: float) -> str:
        """What the benchmark's threads were doing at ``t``: the innermost
        ``bench.*`` annotation, and the innermost event inside it."""
        around = [h for h in self.host if h[0] != "bench.window"
                  and h[1] <= t <= h[1] + h[2]]
        bench = [h for h in around if h[0].startswith("bench.")]
        if not bench:
            return "outside the program's calls"
        span = min(bench, key=lambda h: h[2])
        inner = [h for h in around if h[3] == span[3] and h[2] < span[2]]
        if not inner:
            return span[0]
        return f"{span[0]} > {min(inner, key=lambda h: h[2])[0]}"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps, each named by the innermost host event around its middle."""
        by_op: dict[str, float] = {}
        for name, typ, _k, _s, dur in self.ops:
            key = f"{name} {typ}".strip()
            by_op[key] = by_op.get(key, 0.0) + dur * 1e-9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        b = self._busy
        ws, we = self.window
        edges = np.concatenate([[ws], b.ravel(), [we]]).reshape(-1, 2)
        gaps = [(s, e) for s, e in edges if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for s, e in gaps[:top]:
            named.append([self.host_activity(0.5 * (s + e)), (e - s) * 1e-9])
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


def load(logdir: Path) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(logdir) / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profile under {logdir}")
    return Trace.from_profile(ProfileData.from_file(sorted(files)[-1]))


def save_json(trace: Trace, path: Path) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump(trace.to_json(), fh)


def load_json(path: Path) -> Trace:
    with gzip.open(path, "rt") as fh:
        return Trace.from_json(json.load(fh))


@dataclasses.dataclass
class RunView:
    """What a per-layer reader sees of one traced run."""

    cell: dict
    cfg: dict
    traffic: dict
    model: object
    trace: Optional[Trace]
    records: dict
    setup_parts: dict
    peaks: Optional[dict]
