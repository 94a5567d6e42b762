"""Transfer and return: seconds per request that the request span spends
outside planning, packing and device work: operand uploads, dispatch,
the copy of the result to the host, its unpermute and the output guard
(obs spans less the device's busy seconds per request)."""
UNIT = "s"


def read(ctx):
    parts = [ctx.per_request_s(n) for n in ("request", "plan", "pack")]
    if None in parts or ctx.trace is None or not ctx.served():
        return None
    req, plan, pack = parts
    return req - plan - pack - ctx.trace.busy_s() / ctx.served()
