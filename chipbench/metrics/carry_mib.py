"""Transfer and return: MiB per request that a chain's intermediates
move between hops, the ``bytes`` of the ``carry`` spans (obs spans).
Nothing where the program records no ``carry`` span."""
UNIT = "MiB"


def read(ctx):
    spans = ctx.spans_named("carry")
    n = len(ctx.spans_named("request"))
    if not spans or not n:
        return None
    return sum(s.attrs.get("bytes", 0) for s in spans) / n / 2**20
