"""Pallas TPU kernel: fused Mamba2 SSD chunk scan.

The §Roofline analysis flags SSM train cells as memory-bound: the pure-jnp
chunked SSD (models/mamba2.py) materializes the (Q×Q) decay matrix L, the
chunk states, and the decay vectors to HBM between einsums. This kernel
fuses one (batch·head, chunk) step entirely in VMEM:

  grid = (B·H, n_chunks); the inter-chunk state recurrence rides in a VMEM
  scratch accumulator that persists across the (serial) chunk dimension —
  the same resident-output idiom as the cluster kernel's output accumulation.

Per grid step, entirely in VMEM:
    L       = exp(segsum(a))            (Q, Q) lower-tri
    y_diag  = ((C Bᵀ) ∘ L) · X          intra-chunk
    y_off   = (C h_prev) ∘ exp(a_cum)   inter-chunk readout
    h_new   = h_prev · exp(a_sum) + (B · decay)ᵀ X

Shapes per (b,h): x (nc, Q, P); a (nc, Q); b/c (nc, Q, N). dt is folded
into x and a by the wrapper (ops-level), matching models/mamba2.ssd_chunked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["ssd_chunk_scan"]


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hfin_ref, h_scr, *,
            nchunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0]                      # (Q, P)
    a = a_ref[0, 0]                      # (Q,) log-decay steps
    bmat = b_ref[0, 0]                   # (Q, N)
    cmat = c_ref[0, 0]                   # (Q, N)

    q = a.shape[0]
    a_cum = jnp.cumsum(a)                                # (Q,)
    seg = a_cum[:, None] - a_cum[None, :]                # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)           # lower-tri decay

    scores = jnp.dot(cmat, bmat.T,
                     preferred_element_type=jnp.float32) * L   # (Q, Q)
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    # inter-chunk readout from the carried state
    h_prev = h_scr[...]                                  # (N, P)
    y += jnp.exp(a_cum)[:, None] * jnp.dot(
        cmat, h_prev, preferred_element_type=jnp.float32)

    # state update: h = h_prev * exp(sum a) + Σ_t decay_t B_t x_tᵀ
    decay_state = jnp.exp(a_cum[-1] - a_cum)             # (Q,)
    h_new = h_prev * jnp.exp(a_cum[-1]) + jnp.dot(
        (bmat * decay_state[:, None]).T, x,
        preferred_element_type=jnp.float32)              # (N, P)
    h_scr[...] = h_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nchunks - 1)
    def _fin():
        hfin_ref[0] = h_new.astype(hfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_scan(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
                   *, interpret: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """Fused SSD over chunks.

    Args (already chunked and dt-discretized, f32):
      x (BH, nc, Q, P); a (BH, nc, Q); b/c (BH, nc, Q, N).
    Returns (y (BH, nc, Q, P), final_state (BH, N, P)).
    """
    bh, nc, qq, p = x.shape
    n = b.shape[-1]
    grid = (bh, nc)
    kernel = functools.partial(_kernel, nchunks=nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qq, p), lambda i, ci: (i, ci, 0, 0)),
            pl.BlockSpec((1, 1, qq), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, 1, qq, n), lambda i, ci: (i, ci, 0, 0)),
            pl.BlockSpec((1, 1, qq, n), lambda i, ci: (i, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, qq, p), lambda i, ci: (i, ci, 0, 0)),
            pl.BlockSpec((1, n, p), lambda i, ci: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, qq, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, a, b, c)
