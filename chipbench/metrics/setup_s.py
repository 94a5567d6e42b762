"""Process start to the first timed request: JAX start-up, the operands
from the seed, the compile cache and the warm-up requests (host clock)."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
