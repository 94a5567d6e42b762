"""Front end: 95th percentile of the time a request waited between the
client's ``submit`` and the start of its ``request`` span, when the
server's worker took it up (obs spans on the host clock). The worker
serves in order, so the k-th request span is the k-th admitted
request's."""
from chipbench.readings import nearest_rank

UNIT = "s"


def read(ctx):
    starts = sorted(s.t0 for s in ctx.spans_named("request"))
    sent = sorted(r.sent for r in ctx.records
                  if not r.error.startswith("shed"))
    if not starts or len(starts) != len(sent):
        return None
    return nearest_rank([b - a for a, b in zip(sent, starts)], 0.95)
