"""Kernels: the share of the unmasked live-pair stream that a masked
Sp×Sp launch still runs, Σ ``pairs`` over Σ ``pairs_unmasked`` of the
window's masked ``kernel_variant`` spans (obs spans). Nothing where the
program records no masked launch."""
UNIT = "%"


def read(ctx):
    spans = [s for s in ctx.spans_named("kernel_variant")
             if s.attrs.get("masked")]
    total = sum(s.attrs.get("pairs_unmasked", 0) for s in spans)
    if not total:
        return None
    return 100.0 * sum(s.attrs.get("pairs", 0) for s in spans) / total
