"""Front end: seconds per request in the ``admit`` span (fingerprint,
coalescing digests, queue offer, on the client's thread) and the
``validate`` span (operand checks, on the worker; obs spans). Nothing
where the program records no ``admit`` span."""
UNIT = "s"


def read(ctx):
    if not ctx.spans_named("admit"):
        return None
    parts = [ctx.per_request_s(n) for n in ("admit", "validate")]
    if parts[0] is None:
        return None
    return sum(parts)
