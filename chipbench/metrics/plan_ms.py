"""Planning: milliseconds of the planner's ``plan`` span per request
(fingerprint, plan-cache lookup; obs spans)."""
UNIT = "ms"


def read(ctx):
    s = ctx.per_request_s("plan")
    return None if s is None else 1e3 * s
