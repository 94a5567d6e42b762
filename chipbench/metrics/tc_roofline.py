"""Kernels: the masked Sp×Sp kernel's share of its roofline, the least
time the chip could take for one request's (L·L)∘L (chipbench/work.py
counting: the products that land on the mask, L read three times and C
written on its pattern) over its device seconds per request."""
UNIT = "%"


def read(ctx):
    return ctx.roofline_pct("tc", ctx.value("kernel_device_s.tc"))
