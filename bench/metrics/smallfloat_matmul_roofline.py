"""The smallfloat_matmul kernel's least time (larger of FLOPs over peak and
logical bytes over HBM bandwidth) over its summed device time, in %."""

from bench.readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "smallfloat_matmul")
