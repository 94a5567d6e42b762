"""Host↔device copies of the serving path, each under one obs span.

Every host→device conversion on the Pallas serving path goes through
:func:`to_device` and every device→host copy of packed operands through
:func:`to_host`, so the tracer sees each transfer where it happens: one
``upload`` or ``fetch`` span per call site (not per array), carrying the
``bytes`` moved. While the tracer is enabled, an upload closes on
``block_until_ready`` of what it uploaded, so its duration is the copy
and not its enqueue. Disabled, both are the plain conversions: no span,
no clock read, no byte count.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import get_tracer

__all__ = ["to_device", "to_host", "device_nbytes"]


def device_nbytes(x) -> int:
    """Bytes of ``x`` held on the device (0 for a host array)."""
    return int(x.nbytes) if isinstance(x, jax.Array) else 0


def to_device(*arrays, dtypes: Optional[Sequence] = None) -> tuple:
    """``jnp.asarray`` of each array (with ``dtypes[i]`` when given),
    under one ``upload`` span whose ``bytes`` are those of the arrays
    that came from the host (a device array passes through unmoved)."""
    dts = dtypes if dtypes is not None else (None,) * len(arrays)
    tracer = get_tracer()
    if not tracer.enabled:
        return tuple(jnp.asarray(a, dt) for a, dt in zip(arrays, dts))
    with tracer.span("upload") as sp:
        out = tuple(jnp.asarray(a, dt) for a, dt in zip(arrays, dts))
        jax.block_until_ready(out)
        sp.set(bytes=sum(int(o.nbytes) for a, o in zip(arrays, out)
                         if not isinstance(a, jax.Array)))
    return out


def to_host(*arrays) -> tuple:
    """``np.asarray`` of each array under one ``fetch`` span whose
    ``bytes`` are those of the arrays that were on the device."""
    tracer = get_tracer()
    if not tracer.enabled:
        return tuple(np.asarray(a) for a in arrays)
    with tracer.span("fetch") as sp:
        out = tuple(np.asarray(a) for a in arrays)
        sp.set(bytes=sum(device_nbytes(a) for a in arrays))
    return out
