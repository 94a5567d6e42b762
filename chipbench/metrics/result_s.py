"""Transfer and return: seconds per request in the ``fetch`` spans
(device-to-host copies, the result's unpermute) and the ``guard`` span
(the output's finiteness check; obs spans). Nothing where the program
records no ``fetch`` span."""
UNIT = "s"


def read(ctx):
    if not ctx.spans_named("fetch"):
        return None
    parts = [ctx.per_request_s(n) for n in ("fetch", "guard")]
    if parts[0] is None:
        return None
    return sum(parts)
