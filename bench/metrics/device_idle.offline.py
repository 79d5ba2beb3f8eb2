"""Share of the traced window in which no operation ran on the device, in %."""

from bench.readers import idle_pct as read  # noqa: F401
