"""Mean length of the program's `nest.launch` spans, in µs: the jitted
call's dispatch and the copy of its arguments to the device."""

from bench.spanreaders import mean_us


def read(view):
    return mean_us(view, "nest.launch")
