"""Kernels: device seconds per request in the Sp×Sp Pallas kernel of
the masked square (L·L)∘L (profiler trace: the jitted programs that
launch it, found by the name of the launching function)."""
PATTERN = r"^jit_cluster_spgemm"
UNIT = "s"


def read(ctx):
    return ctx.kernel_device_s(PATTERN)
