"""Median latency of every request of the window, from when it was due
(open loop) or sent (closed loop) to its response; a request that failed
counts as never answered (host clock)."""
from chipbench.readings import nearest_rank

UNIT = "s"


def read(ctx):
    return nearest_rank(ctx.latencies(), 0.50)
