"""Transfer and return: seconds per request in the ``carry`` spans, an
intermediate's trip between two hops of a chain (its copy to the host,
its conversion, the next hop's refill; obs spans). Nothing where the
program records no ``carry`` span."""
UNIT = "s"


def read(ctx):
    if not ctx.spans_named("carry"):
        return None
    return ctx.per_request_s("carry")
