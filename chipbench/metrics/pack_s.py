"""Host packing: seconds of the ``pack`` spans per request (BCC and
tiled packing, compact and live-pair streams on an exec-cache miss;
obs spans). Nothing where no request of the window packed."""
UNIT = "s"


def read(ctx):
    if not ctx.spans_named("pack"):
        return None
    return ctx.per_request_s("pack")
