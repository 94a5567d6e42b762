"""The control's arithmetic: a float32 product at the precision just
below the configurations' fp32-at-highest. Each operand is split into a
bf16 high part and a bf16 low part, and three of the four cross products
(all but low·low) are accumulated in fp32: the three-pass bf16 scheme
that ``precision=HIGH`` asks of the MXU, written out so that it computes
the same on any backend."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bf16(x):
    # reduce_precision rounds as the conversion would; a bare
    # f32 -> bf16 -> f32 round trip may be folded away by the compiler
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi.astype(jnp.bfloat16), _bf16(x - hi).astype(jnp.bfloat16)


@jax.jit
def matmul_bf16x3(a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.jit
def matmul_high(a, b):
    """The same product with the backend's own ``precision=HIGH`` (on a
    TPU, three bf16 passes on the MXU; elsewhere as the backend likes)."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
