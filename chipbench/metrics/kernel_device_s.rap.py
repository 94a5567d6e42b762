"""Kernels: device seconds per request in the Sp×Sp Pallas kernels of
both hops of R·(A·P) (profiler trace: the jitted programs that launch
them, found by the name of the launching function)."""
PATTERN = r"^jit_cluster_spgemm"
UNIT = "s"


def read(ctx):
    return ctx.kernel_device_s(PATTERN)
