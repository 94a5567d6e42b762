"""Pallas TPU kernel: cluster-wise SpMM over the BCC format.

This is the TPU-native realization of the paper's cluster-wise dataflow
(Alg. 1) for the square × tall-skinny workload (§4.4): ``C = A @ B`` with A
sparse in Block-Clustered-Columns and B dense.

Dataflow ↔ paper correspondence
  * a *cluster* is a ``block_r``-row block of the (reordered) A matrix;
  * the per-cluster deduplicated column list becomes the per-block active
    ``block_k``-wide B *tile* list (``tile_ids``);
  * "keep the B row in cache while processing all rows of the cluster"
    becomes "keep the B tile in VMEM for one grid step and multiply it
    against the whole (block_r × block_k) cluster slab on the MXU".

Two variants:

``cluster_spmm``  (v1, padded grid)
    grid = (n_tiles_N, nblocks, tiles_per_block). Every block visits its full
    padded tile list; padding slots point at B tile 0 with an all-zero A slab
    (correct, but wasted MXU issue slots when occupancy is ragged).

``cluster_spmm_compact``  (v2, compact grid — the §Perf hillclimbed variant)
    grid = (n_tiles_N, total_live_tiles). The tile stream enumerates *only
    live* (block, tile) pairs; a scalar-prefetched ``block_ids`` array routes
    each step's output block, and the accumulator re-initializes exactly when
    the block id changes. Removes all padding compute: the win equals the
    suite-average padding fraction (measured in EXPERIMENTS.md §Perf).

Scalar prefetch (``pltpu.PrefetchScalarGridSpec``) is what lets the B
BlockSpec's ``index_map`` be *data-dependent* — the indirection at the heart
of any sparse-on-TPU kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunked import open_window, run_in_chunks

# fp32 contractions at full fp32 precision: at the MXU's default precision
# for fp32 operands, a Graph500 scale-14 A·A on a TPU v5e came back off
# the fp32 product by up to 3.4e-4 of max|C|
_FP32 = jax.lax.Precision.HIGHEST

__all__ = ["cluster_spmm", "cluster_spmm_compact"]


# ---------------------------------------------------------------------------
# v1: padded grid
# ---------------------------------------------------------------------------


def _spmm_kernel_padded(ids_ref, a_ref, b_ref, o_ref):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[0]                      # (block_r, block_k)
    b = b_ref[...].astype(jnp.float32)   # (block_k, bn)
    o_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32,
                          precision=_FP32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "tiles_per_block", "bn", "interpret"))
def cluster_spmm(tile_ids: jax.Array, a_values: jax.Array, b: jax.Array,
                 *, block_r: int, block_k: int, tiles_per_block: int,
                 bn: int = 128, interpret: bool = False) -> jax.Array:
    """C = A_bcc @ B.

    Args:
      tile_ids: (nblocks * tiles_per_block,) int32 — active B-tile ids per
        block, padded with 0 (padding slabs must be zero).
      a_values: (nblocks * tiles_per_block, block_r, block_k) — value slabs.
      b: (K, N) dense; K must be a multiple of block_k, N of bn.

    Returns: (nblocks * block_r, N) dense C.
    """
    nslabs, br, bk = a_values.shape
    assert (br, bk) == (block_r, block_k)
    nblocks = nslabs // tiles_per_block
    k, n = b.shape
    assert k % block_k == 0 and n % bn == 0, (k, n, block_k, bn)

    grid = (n // bn, nblocks, tiles_per_block)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, block_k),
                         lambda j, bidx, t, ids: (bidx * tiles_per_block + t,
                                                  0, 0)),
            pl.BlockSpec((block_k, bn),
                         lambda j, bidx, t, ids:
                         (ids[bidx * tiles_per_block + t], j)),
        ],
        out_specs=pl.BlockSpec((block_r, bn),
                               lambda j, bidx, t, ids: (bidx, j)),
    )
    return pl.pallas_call(
        _spmm_kernel_padded,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((nblocks * block_r, n), b.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_ids, a_values, b)


# ---------------------------------------------------------------------------
# v2: compact grid (no padding compute)
# ---------------------------------------------------------------------------


def _spmm_kernel_compact(s_total, block_r, bn, meta_ref, block_ids_ref,
                         tile_ids_ref, a_ref, b_ref, c_hbm, o_ref, sem):
    j = pl.program_id(0)
    s = pl.program_id(1)

    def window(blk):
        return c_hbm.at[pl.ds(pl.multiple_of(blk * block_r, block_r),
                              block_r),
                        pl.ds(pl.multiple_of(j * bn, bn), bn)]

    open_window(s, block_ids_ref, meta_ref, o_ref, window, sem)

    @pl.when(meta_ref[1] + s < s_total)       # chunk tail pads: no MXU
    def _acc():
        o_ref[...] += jnp.dot(a_ref[0], b_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=_FP32
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "nblocks", "bn", "chunk", "interpret"))
def cluster_spmm_compact(block_ids: jax.Array, tile_ids: jax.Array,
                         a_values: jax.Array, b: jax.Array,
                         *, block_r: int, block_k: int, nblocks: int,
                         bn: int = 128, chunk: int | None = None,
                         interpret: bool = False) -> jax.Array:
    """Compact-stream variant: only live (block, tile) pairs are visited.

    Args:
      block_ids: (S,) int32, non-decreasing — owning row-block of each live
        tile. May be padded at the END by repeating the last block id with
        zero slabs.
      tile_ids: (S,) int32 — B tile id per live tile.
      a_values: (S, block_r, block_k) value slabs.
      b: (K, N) dense.
      chunk: most stream steps per launch — the two streams are
        scalar-prefetched into SMEM, so long streams run as several
        launches (:mod:`repro.kernels.chunked`); ``None`` is one launch.
    """
    s_total, br, bk = a_values.shape
    assert (br, bk) == (block_r, block_k)
    k, n = b.shape
    assert k % block_k == 0 and n % bn == 0
    last = s_total - 1

    def launch(meta, blks, ids, c):
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // bn, blks.shape[0]),
            in_specs=[
                pl.BlockSpec((1, block_r, block_k),
                             lambda j, s, m, blks, ids:
                             (jnp.minimum(m[1] + s, last), 0, 0)),
                pl.BlockSpec((block_k, bn),
                             lambda j, s, m, blks, ids: (ids[s], j)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((block_r, bn),
                                   lambda j, s, m, blks, ids: (blks[s], j)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        )
        return pl.pallas_call(
            functools.partial(_spmm_kernel_compact, s_total, block_r, bn),
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
            input_output_aliases={5: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(meta, blks, ids, a_values, b, c)

    c0 = jnp.zeros((nblocks * block_r, n), b.dtype)
    return run_in_chunks(launch, (block_ids, tile_ids), c0, slot_pos=None,
                         chunk=chunk)
