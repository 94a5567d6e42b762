"""Kernels: XLA backend compiles inside the window, the ``compile``
spans the program records from ``jax.monitoring`` (obs spans). Nothing
where the program has no such listener."""
UNIT = "count"


def read(ctx):
    from repro.obs import trace as obs_trace
    if ctx.spans is None or not hasattr(obs_trace, "COMPILE_EVENT"):
        return None
    return float(len(ctx.spans_named("compile")))
