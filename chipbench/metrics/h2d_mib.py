"""Transfer and return: MiB copied from the host to the device per
request, the ``bytes`` of the ``upload`` spans (obs spans). Nothing
where the program records no ``upload`` span."""
UNIT = "MiB"


def read(ctx):
    spans = ctx.spans_named("upload")
    n = len(ctx.spans_named("request"))
    if not spans or not n:
        return None
    return sum(s.attrs.get("bytes", 0) for s in spans) / n / 2**20
