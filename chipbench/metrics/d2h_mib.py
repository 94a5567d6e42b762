"""Transfer and return: MiB copied from the device to the host per
request, the ``bytes`` of the ``fetch`` spans: the result, and packed
operands read back while packing (obs spans). Nothing where the program
records no ``fetch`` span."""
UNIT = "MiB"


def read(ctx):
    spans = ctx.spans_named("fetch")
    n = len(ctx.spans_named("request"))
    if not spans or not n:
        return None
    return sum(s.attrs.get("bytes", 0) for s in spans) / n / 2**20
