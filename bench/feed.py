"""Seeded inputs and arrival schedules: the benchmark's own generators.

Copies, kept here so that the yardstick does not move with the program:

* :class:`DetectorFeed` renders frames exactly as ``repro.trigger.stream``'s
  generator of the same name does (same draws, same order), but renders them
  in set-up: :meth:`DetectorFeed.frames` then only hands them out, so that
  the producer thread paces frames during the window and renders none.
* :func:`bursty_schedule` is ``benchmarks/bench_serving.py``'s open-loop
  arrival shape: Poisson arrivals at a base rate, with ``burst_len`` of every
  ``burst_every`` requests drawn at the burst rate.  Its gaps are stratified
  (see the function), so that the seed orders the load and does not change
  how much of it there is.

Both are pure functions of their seed.  Seeds may be any non-negative whole
number (the driver's exceed 32 bits); ``numpy.random.default_rng`` takes them
as they are.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterator

import numpy as np


@dataclasses.dataclass
class Frame:
    """One detector frame, with the fields ``TriggerLoop`` reads and sets."""

    frame_id: int
    data: np.ndarray              # the (1, 1, img, img) input memref
    t_sched: float                # due offset from the producer's start (s)
    n_peaks: int
    arrival_t: float = 0.0        # set by the loop's producer on arrival


@dataclasses.dataclass
class DetectorFeed:
    """Gaussian pixel noise; with probability ``event_rate`` one Gaussian
    peak; every ``pileup_every`` frames a burst of ``pileup_len`` frames with
    ``pileup_peaks`` peaks each."""

    img: int = 11
    frame_rate_hz: float = 1000.0
    event_rate: float = 0.6
    pileup_every: int = 50
    pileup_len: int = 5
    pileup_peaks: int = 3
    noise: float = 0.05
    amplitude: tuple = (0.6, 1.4)
    sigma: tuple = (0.8, 1.6)
    seed: int = 0

    def __post_init__(self):
        self._pool: list[tuple[np.ndarray, int]] = []
        self.t_start: float = 0.0
        self.handed_out: list[Frame] = []

    def _render(self, rng: np.random.Generator, n_peaks: int) -> np.ndarray:
        img = self.img
        frame = rng.normal(0.0, self.noise, (img, img)).astype(np.float32)
        yy, xx = np.mgrid[0:img, 0:img].astype(np.float32)
        for _ in range(n_peaks):
            cy, cx = rng.uniform(1.0, img - 2.0, 2)
            amp = rng.uniform(*self.amplitude)
            sig = rng.uniform(*self.sigma)
            frame += (amp * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2)
                                   / (2.0 * sig * sig))).astype(np.float32)
        return frame[None, None]

    def render(self, n: int) -> np.ndarray:
        """Render the first ``n`` frames of the stream into the pool and
        return them stacked, ``(n, 1, 1, img, img)``."""
        rng = np.random.default_rng(self.seed)
        self._pool = []
        for i in range(n):
            if self.pileup_every and i % self.pileup_every < self.pileup_len:
                n_peaks = self.pileup_peaks
            else:
                n_peaks = int(rng.random() < self.event_rate)
            self._pool.append((self._render(rng, n_peaks), n_peaks))
        return np.stack([d for d, _ in self._pool])

    def frames(self, n: int) -> Iterator[Frame]:
        """Hand out ``n`` frames, cycling the rendered pool.

        The first call of ``next`` marks the producer's start: frame ``i``
        is due at ``t_start + i / frame_rate_hz`` on ``time.perf_counter``.
        """
        if not self._pool:
            raise RuntimeError("render() the pool in set-up first")
        self.t_start = time.perf_counter()
        self.handed_out = []
        dt = 1.0 / self.frame_rate_hz
        for i in range(n):
            data, n_peaks = self._pool[i % len(self._pool)]
            frame = Frame(frame_id=i, data=data, t_sched=i * dt,
                          n_peaks=n_peaks)
            self.handed_out.append(frame)
            yield frame


def bursty_schedule(n: int, base_rate: float, burst_rate: float,
                    burst_every: int, burst_len: int, seed: int
                    ) -> np.ndarray:
    """Arrival offsets in seconds from the load's start, increasing.

    Request ``i`` belongs to a burst when ``i % burst_every < burst_len``
    and its gap to the previous request is exponential at the burst rate,
    else at the base rate.  The gaps of each class are the exponential
    distribution's quantiles at ``(k + 0.5) / count``, shuffled by ``seed``:
    every seed offers the same set of gaps, so the same work over the same
    span, in another order.
    """
    idx = np.arange(n)
    in_burst = (idx % burst_every) < burst_len
    rng = np.random.default_rng(seed)
    gaps = np.empty(n)
    for mask, rate in ((in_burst, burst_rate), (~in_burst, base_rate)):
        k = int(mask.sum())
        q = (np.arange(k) + 0.5) / max(k, 1)
        gaps[mask] = rng.permutation(-np.log1p(-q) / rate)
    return np.cumsum(gaps)
