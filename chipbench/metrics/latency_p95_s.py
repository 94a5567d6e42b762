"""95th percentile latency of every request of the window, timed from
its due time; a request that failed counts as never answered (host
clock)."""
from chipbench.readings import nearest_rank

UNIT = "s"


def read(ctx):
    return nearest_rank(ctx.latencies(), 0.95)
