"""Model FLOPs per sample times the samples per second of the traced
window, over the chip's peak, in %."""

from bench.readers import mfu_pct as read  # noqa: F401
