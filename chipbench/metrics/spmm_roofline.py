"""Kernels: the SpMM kernel's share of its roofline (as sxs_roofline,
for one request's Â·X)."""
UNIT = "%"


def read(ctx):
    return ctx.roofline_pct("spmm", ctx.value("kernel_device_s.spmm"))
