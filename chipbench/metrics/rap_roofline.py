"""Kernels: the Sp×Sp kernels' share of their roofline over both hops
of R·(A·P), the least time the chip could take for one request's two
products (chipbench/work.py) over their device seconds per request."""
UNIT = "%"


def read(ctx):
    return ctx.roofline_pct("rap", ctx.value("kernel_device_s.rap"))
