"""Host µs per nest-tier call spent before the launch: the program's
`nest.feeds` and `nest.weights` spans summed over the window, per
`nest.call` span."""

from bench.spanreaders import prep_us_per_call as read  # noqa: F401
