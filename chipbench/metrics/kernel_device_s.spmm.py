"""Kernels: device seconds per request in the SpMM Pallas kernel
(profiler trace: the jitted programs that launch them, found by
the name of the launching function)."""
PATTERN = r"^jit_cluster_spmm"
UNIT = "s"


def read(ctx):
    return ctx.kernel_device_s(PATTERN)
