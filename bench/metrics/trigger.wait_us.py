"""Mean length of the program's `trigger.wait` spans, in µs: from the end
of one decision to the start of the next, polling the ring and filling a
partial window."""

from bench.spanreaders import mean_us


def read(view):
    return mean_us(view, "trigger.wait")
