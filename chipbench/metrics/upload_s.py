"""Transfer and return: seconds per request in the ``upload`` spans,
each a host-to-device copy closed on the device's completion (packed
operands, compact streams, pairs, dense X; those inside ``pack`` too;
obs spans). Nothing where the program records no ``upload`` span."""
UNIT = "s"


def read(ctx):
    if not ctx.spans_named("upload"):
        return None
    return ctx.per_request_s("upload")
