"""Launching a scalar-prefetched Pallas stream in fixed-length chunks.

A ``PrefetchScalarGridSpec`` kernel gets every scalar-prefetched array
whole in the TensorCore's SMEM, so a pair stream of millions of steps
cannot be one launch. :func:`run_in_chunks` cuts the streams into
launches of one length (so one compile serves every chunk) and threads
the output through them by aliasing: a launch writes back only the
output windows it visits, and every other window keeps what the earlier
launches wrote.

A window whose steps straddle two chunks is not zeroed again: at a
chunk's first step, :func:`open_window` reloads the window's partial
sums from the aliased output instead. The sums then continue in the same
order as in one launch, so the result is bit-identical to the unchunked
stream.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["run_in_chunks", "open_window"]


def _chunk_streams(streams: Sequence[jax.Array], slot_pos: Optional[int],
                   chunk: Optional[int]) -> list[jax.Array]:
    """(T,) streams → (n, L) chunks of one length ``L <= chunk`` (a
    multiple of 8; ``chunk=None`` is one launch). The tail pads with
    repeats of each stream's last entry, except stream ``slot_pos``,
    which pads with the zero slot (no MXU issue) — the tail convention of
    :func:`repro.core.formats.live_pair_stream`."""
    t = streams[0].shape[0]
    n = 1 if chunk is None else -(-t // chunk)
    length = (-(-t // n) + 7) // 8 * 8
    pad = n * length - t
    out = []
    for i, s in enumerate(streams):
        if pad:
            fill = (jnp.zeros((pad,), s.dtype) if i == slot_pos
                    else jnp.full((pad,), s[-1], s.dtype))
            s = jnp.concatenate([s, fill])
        out.append(s.reshape(n, length))
    return out


def run_in_chunks(launch: Callable, streams: Sequence[jax.Array], c0:
                  jax.Array, *, slot_pos: Optional[int],
                  chunk: Optional[int]) -> jax.Array:
    """Run ``launch(meta, *chunk_streams, c) -> c`` over ``streams``.

    ``streams[0]`` is the window key: the output window a step writes
    changes exactly where the key does. ``meta`` is an int32 ``(2,)``
    array holding the previous chunk's last key (-1 before the first
    chunk) and the stream index of the chunk's first step. ``c0`` is the
    output before any launch; an empty stream returns it unchanged.
    """
    if streams[0].shape[0] == 0:
        return c0
    parts = _chunk_streams(streams, slot_pos, chunk)
    n, length = parts[0].shape

    def body(k, c):
        prev = jnp.where(k > 0, parts[0][jnp.maximum(k - 1, 0), -1], -1)
        meta = jnp.stack([prev, k * length]).astype(jnp.int32)
        return launch(meta, *(p[k] for p in parts), c)

    if n == 1:
        return body(0, c0)
    return jax.lax.fori_loop(0, n, body, c0)


def open_window(t, key_ref, meta_ref, o_ref, window: Callable, sem):
    """Open the output window of chunk step ``t``: zero it when the key
    changes there, or — at the chunk's first step, when the window
    continues the previous chunk's last one — DMA its partial sums back
    from ``window(key)``, the window's view of the aliased output."""
    key = key_ref[t]
    before = jnp.where(t == 0, meta_ref[0], key_ref[jnp.maximum(t - 1, 0)])

    @pl.when(key != before)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((t == 0) & (key == before))
    def _carry():
        cp = pltpu.make_async_copy(window(key), o_ref, sem.at[0])
        cp.start()
        cp.wait()
