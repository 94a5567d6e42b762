"""Kernels: the Sp×Sp kernel's share of its roofline, the least time
the chip could take for one request's A·B (chipbench/work.py: useful
flops over the bf16 peak, or compulsory bytes over HBM bandwidth,
whichever is longer) over its device seconds per request."""
UNIT = "%"


def read(ctx):
    return ctx.roofline_pct("sxs", ctx.value("kernel_device_s.sxs"))
