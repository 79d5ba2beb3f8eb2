"""Host µs per nest-tier call that the device does not overlap: each
`bench.call` span less the device-busy time inside it, as a mean."""

from bench.readers import host_us_per_call as read  # noqa: F401
