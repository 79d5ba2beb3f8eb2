"""Share of the traced window in which the device idles inside the
program's `nest.call` spans (the nest tier's host path), in %."""

from bench.spanreaders import idle_in_calls_pct as read  # noqa: F401
