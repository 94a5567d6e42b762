"""Pallas TPU kernel: cluster-wise sparse × sparse SpGEMM on the MXU.

This is the TPU-native realization of the paper's cluster-wise dataflow for
the sparse × sparse workload (§4.2–4.3, ``C = A·B`` with both operands
sparse — the A² case in the paper): A is packed in BCC
(block-clustered-columns, ``core.formats.BCC``) and B in the tiled-sparse
``core.formats.TiledCSR`` — dense ``(block_k, bn)`` slabs for B's *live*
tiles plus a flat (k-block, n-tile) → tile-slot lookup table.

Dataflow ↔ paper correspondence
  * a *cluster* is a ``block_r``-row block of the (reordered) A matrix;
  * "keep the B rows in cache while processing all rows of the cluster"
    becomes "keep the B tile in VMEM and contract it against the whole
    ``(block_r × block_k)`` cluster slab on the MXU" — one B fetch serves
    every row of the cluster at once;
  * the row-wise baseline's per-nonzero B-row gather (8 B of index+value
    per element, re-fetched per A nonzero) becomes a dense, index-free
    tile stream.

The **double indirection** is the heart of the kernel: the compact
(block, k-tile) stream of A (``bcc_compact_stream``) is scalar-prefetched,
and each step chases A's k-tile id through B's tile table to find the B
slab to multiply::

    slot = table[tile_ids[s] * nnb + j]      # 0 = dead → skip the MXU op

Two variants, differing in where B lives:

``cluster_spgemm_tiled``  (streamed B)
    grid = (nnb, S). B tiles stay in HBM; the B BlockSpec's index_map
    performs the table lookup, so each grid step DMAs exactly the one tile
    it contracts (Pallas elides the copy when consecutive steps land on
    the same tile). Scales to B far larger than VMEM.

``cluster_spgemm_resident``  (VMEM-resident B)
    Same grid, but the whole tile store is pinned in VMEM (constant
    index_map → fetched from HBM exactly once) and the kernel indexes it
    dynamically. For suite-sized operands this makes B's total HBM
    traffic equal its live-tile footprint — the "pays the bandwidth of
    *its* footprint" endpoint. Use when ``tiles.nbytes`` fits the VMEM
    budget (the ops-layer wrapper auto-selects).

Accumulator re-initialization on block-id change mirrors
``cluster_spmm_compact``; dead table slots predicate away their MXU issue
with ``pl.when`` so fully-sparse B column strips cost no FLOPs.

Sparsity-compacted grid (v2 — the ``*_pairs`` kernels)
------------------------------------------------------

The ``(nnb, S)`` grid above still *walks* every dead pair: a grid step and
an A-slab DMA per (stream step, column strip) whose B tile is dead, and A
re-fetched ``nnb`` times unconditionally. The v2 kernels take the
host-compacted stream of live ``(s, j, slot)`` triples
(:func:`repro.core.formats.live_pair_stream`, ordered (block, s, j)) and
run a flat 1-D grid over it:

  * grid steps ≈ actual MXU contractions (+ one zero-slot sentinel per
    pair-less block, the ``cover_all_blocks`` convention);
  * the C output window is the block's whole ``(block_r, nnb*bn)`` row
    strip, zero-initialized once on block entry — so a fully-dead
    ``(block, j)`` strip costs nothing yet still reads back zero;
  * pairs sharing a stream step are adjacent, so Pallas elides the
    repeated A DMA: each A slab is fetched once per stream step total.

Variants: ``cluster_spgemm_pairs`` (streamed B, one tile DMA per step),
``cluster_spgemm_pairs_resident`` (B store pinned in VMEM),
``cluster_spgemm_pairs_db`` (streamed B behind a two-slot VMEM scratch
with manual async copies — the tile for step t+1 is in flight while step
t contracts). All three accept fp32 or bf16 B tiles; bf16 halves B's HBM
bytes and is upcast at the MXU input, accumulation stays fp32.

Multi-core sharding + B-fetch-deduping revisit order (v3)
---------------------------------------------------------

``cluster_spgemm_pairs_sharded`` scales the pair stream across TPU cores:
the host partitions the stream into contiguous block ranges balanced by
live-pair count (:func:`repro.core.formats.partition_pair_stream`) and a
``shard_map`` over a 1-D core mesh runs each core's sub-stream against
its own C row-strip range — blocks own disjoint C rows, so no cross-core
accumulation is needed. Off-TPU (or on one device) the same partition
runs serially, so results are identical everywhere.

Sparse-C output (v4 — the two-phase pipeline's numeric phase)
-------------------------------------------------------------

Every kernel above writes *dense* C row strips — ``rows × nnb·bn`` HBM
bytes regardless of nnz(C). ``cluster_spgemm_pairs_sparse{,_db}`` take a
window-major re-sort of the live-pair stream (each pair tagged with its
destination ``CompactedC`` slab from the symbolic pass's table) and emit
only the *live* ``(block_r, bn)`` C windows as packed slabs: the VMEM
accumulator is one window, zero-initialized on window entry and written
back once on window exit — the windowed-scatter epilogue happens in the
kernel's output BlockSpec itself, so C bytes written scale with nnz(C)'s
window footprint. Within a window pairs stay s-ascending, so each C
element sees the same fp32 accumulation order as the dense-strip kernels
— bit-identical values, compacted layout.

``cluster_spgemm_pairs_window`` runs a *revisit-ordered* stream
(:func:`repro.core.formats.revisit_pair_stream`): triples sharing a B
tile sit adjacent across blocks, so the streamed-B DMA elision fetches
each live tile once per window instead of once per touching block. The
price is a wider C output window — ``window_blocks`` consecutive block
strips, zero-initialized on window entry — and the loss of A-slab
adjacency (A refetches rise; the ``live_pair_counters`` report both
sides of that trade, and ``bench_kernels`` gates the B-refetch win).

Chunked launches
----------------

A scalar-prefetched stream lives whole in SMEM (1 MiB on a v5e), so every
pair-stream kernel runs its stream as launches of at most ``chunk`` steps
(:mod:`repro.kernels.chunked`), bit-identical to one launch. The padded
``(nnb, S)`` grid is not chunked: it prefetches B's whole tile table, and
the ops layer refuses it when that does not fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.transfer import to_device
from repro.kernels.chunked import open_window, run_in_chunks

# fp32 contractions at full fp32 precision: at the MXU's default precision
# for fp32 operands, a Graph500 scale-14 A·A on a TPU v5e came back off
# the fp32 product by up to 3.4e-4 of max|C|
_FP32 = jax.lax.Precision.HIGHEST

__all__ = ["cluster_spgemm_tiled", "cluster_spgemm_resident",
           "cluster_spgemm_pairs", "cluster_spgemm_pairs_resident",
           "cluster_spgemm_pairs_db", "cluster_spgemm_pairs_window",
           "cluster_spgemm_pairs_sharded", "cluster_spgemm_pairs_sparse",
           "cluster_spgemm_pairs_sparse_db"]


def _is_block_start(block_ids_ref, s):
    return jnp.where(s == 0, True,
                     block_ids_ref[s] != block_ids_ref[jnp.maximum(s - 1, 0)])


# ---------------------------------------------------------------------------
# v1: streamed B tiles (general case — B larger than VMEM)
# ---------------------------------------------------------------------------


def _spgemm_kernel_streamed(nnb, block_ids_ref, tile_ids_ref, table_ref,
                            a_ref, b_ref, o_ref):
    j = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(_is_block_start(block_ids_ref, s))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slot = table_ref[tile_ids_ref[s] * nnb + j]

    @pl.when(slot > 0)                     # dead B tile: no MXU issue
    def _acc():
        o_ref[...] += jnp.dot(a_ref[0], b_ref[0].astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=_FP32
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nblocks", "nnb", "interpret"))
def cluster_spgemm_tiled(block_ids: jax.Array, tile_ids: jax.Array,
                         table: jax.Array, a_values: jax.Array,
                         b_tiles: jax.Array, *, block_r: int, block_k: int,
                         bn: int, nblocks: int, nnb: int,
                         interpret: bool = False) -> jax.Array:
    """C = A_bcc @ B_tiled, streaming one B tile per grid step.

    Args:
      block_ids: (S,) int32, non-decreasing — owning row-block of each live
        (block, k-tile) pair of A. Every row block MUST appear at least
        once (pad empty blocks with a zero slab) so its C strip is zeroed.
      tile_ids: (S,) int32 — A k-tile id per stream step.
      table: (nkb * nnb,) int32 — B's tile lookup table (0 = dead).
      a_values: (S, block_r, block_k) — A cluster slabs.
      b_tiles: (tile_cap, block_k, bn) — B's dense live tiles; slab 0 is
        the all-zero tile dead table entries point at.

    Returns: (nblocks * block_r, nnb * bn) dense C.
    """
    s_total, br, bk = a_values.shape
    assert (br, bk) == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn), (b_tiles.shape, block_k, bn)

    grid = (nnb, s_total)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, block_k),
                         lambda j, s, blks, ids, tbl: (s, 0, 0)),
            pl.BlockSpec((1, block_k, bn),
                         lambda j, s, blks, ids, tbl:
                         (tbl[ids[s] * nnb + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, bn),
                               lambda j, s, blks, ids, tbl: (blks[s], j)),
    )
    return pl.pallas_call(
        functools.partial(_spgemm_kernel_streamed, nnb),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((nblocks * block_r, nnb * bn),
                                       b_tiles.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cluster_spgemm_tiled",
    )(block_ids, tile_ids, table, a_values, b_tiles)


# ---------------------------------------------------------------------------
# v2: VMEM-resident B (footprint-bound traffic — B fetched from HBM once)
# ---------------------------------------------------------------------------


def _spgemm_kernel_resident(nnb, block_ids_ref, tile_ids_ref, table_ref,
                            a_ref, b_ref, o_ref):
    j = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(_is_block_start(block_ids_ref, s))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slot = table_ref[tile_ids_ref[s] * nnb + j]

    @pl.when(slot > 0)
    def _acc():
        o_ref[...] += jnp.dot(a_ref[0], b_ref[slot].astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=_FP32
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nblocks", "nnb", "interpret"))
def cluster_spgemm_resident(block_ids: jax.Array, tile_ids: jax.Array,
                            table: jax.Array, a_values: jax.Array,
                            b_tiles: jax.Array, *, block_r: int,
                            block_k: int, bn: int, nblocks: int, nnb: int,
                            interpret: bool = False) -> jax.Array:
    """Same contract as :func:`cluster_spgemm_tiled`, but the whole B tile
    store is pinned in VMEM (constant index_map — one HBM fetch total) and
    the double indirection resolves to a dynamic VMEM index."""
    s_total, br, bk = a_values.shape
    assert (br, bk) == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn)
    tile_cap = b_tiles.shape[0]

    grid = (nnb, s_total)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, block_k),
                         lambda j, s, blks, ids, tbl: (s, 0, 0)),
            pl.BlockSpec((tile_cap, block_k, bn),
                         lambda j, s, blks, ids, tbl: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, bn),
                               lambda j, s, blks, ids, tbl: (blks[s], j)),
    )
    return pl.pallas_call(
        functools.partial(_spgemm_kernel_resident, nnb),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((nblocks * block_r, nnb * bn),
                                       b_tiles.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cluster_spgemm_resident",
    )(block_ids, tile_ids, table, a_values, b_tiles)


# ---------------------------------------------------------------------------
# v2: sparsity-compacted live-pair grid (see module docstring)
# ---------------------------------------------------------------------------


def _rows(c_hbm, rows):
    """Window view of the aliased C for a window key: ``rows`` rows from
    row ``key * rows``."""
    return lambda key: c_hbm.at[pl.ds(pl.multiple_of(key * rows, rows),
                                      rows)]


def _stream_call(kernel, streams, a_values, b_tiles, *, name, slot_pos,
                 chunk, in_specs, out_spec, out_shape, scratch=(),
                 interpret):
    """Run a 1-D pair-stream kernel over ``streams`` in chunk launches
    (:func:`repro.kernels.chunked.run_in_chunks`). Each launch
    scalar-prefetches ``(meta, *chunk streams)``, reads A and B through
    ``in_specs`` and writes the fp32 ``out_shape`` output, which it takes
    in again — unblocked, for :func:`repro.kernels.chunked.open_window` —
    and aliases to its result. The kernel's last scratch operand is the
    window-carry DMA semaphore. ``name`` names the Pallas kernel (its
    launcher's name), so it reads the same in every profile."""
    npre = 1 + len(streams)

    def launch(meta, *args):
        *chunks, c = args
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=npre,
            grid=(chunks[0].shape[0],),
            in_specs=[*in_specs, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_spec,
            scratch_shapes=[*scratch, pltpu.SemaphoreType.DMA((1,))],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
            input_output_aliases={npre + 2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(meta, *chunks, a_values, b_tiles, c)

    return run_in_chunks(launch, streams, jnp.zeros(out_shape, jnp.float32),
                         slot_pos=slot_pos, chunk=chunk)


def _mxu_acc(a_slab, b_tile, o_ref, col, bn):
    """One contraction into the output row strip, fp32 accumulate; bf16 B
    tiles are upcast at the MXU input (their bytes were saved in HBM)."""
    prod = jnp.dot(a_slab, b_tile.astype(jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=_FP32)
    o_ref[:, pl.ds(col, bn)] += prod.astype(o_ref.dtype)


def _spgemm_kernel_pairs(bn, block_r, meta_ref, blk_ref, j_ref, slot_ref,
                         aidx_ref, a_ref, b_ref, c_hbm, o_ref, sem):
    t = pl.program_id(0)
    # one zero-fill per block: every (block, j) strip, dead or live
    open_window(t, blk_ref, meta_ref, o_ref, _rows(c_hbm, block_r), sem)

    @pl.when(slot_ref[t] > 0)        # sentinels / tail pads: no MXU issue
    def _acc():
        col = pl.multiple_of(j_ref[t] * bn, bn)
        _mxu_acc(a_ref[0], b_ref[0], o_ref, col, bn)


def _pair_specs(block_r, block_k, bn, nnb, b_spec):
    """A-slab spec, the given B spec, and the block's C row-strip spec of
    the (meta, blocks, js, slots, a_idx) prefetch layout."""
    a_spec = pl.BlockSpec((1, block_r, block_k),
                          lambda t, m, blks, js_, sl, ai: (ai[t], 0, 0))
    out_spec = pl.BlockSpec((block_r, nnb * bn),
                            lambda t, m, blks, js_, sl, ai: (blks[t], 0))
    return [a_spec, b_spec], out_spec


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nblocks", "nnb", "chunk", "interpret"))
def cluster_spgemm_pairs(blocks: jax.Array, js: jax.Array, slots: jax.Array,
                         a_idx: jax.Array, a_values: jax.Array,
                         b_tiles: jax.Array, *, block_r: int, block_k: int,
                         bn: int, nblocks: int, nnb: int,
                         chunk: int | None = None,
                         interpret: bool = False) -> jax.Array:
    """C = A_bcc @ B_tiled over the live-pair compacted grid, streaming
    one B tile per live contraction.

    Args:
      blocks/js/slots/a_idx: the (T,) live-pair stream of
        :func:`repro.core.formats.live_pair_stream` — ordered (block, s,
        j), one zero-slot sentinel per pair-less block, tail zero-slot
        padded.
      a_values: (S, block_r, block_k) A cluster slabs (the compact
        stream's slab array; ``a_idx`` indexes it).
      b_tiles: (tile_cap, block_k, bn) fp32 or bf16 dense live tiles;
        slab 0 is the reserved zero tile.
      chunk: most pairs per launch — the stream is scalar-prefetched into
        SMEM, so long streams run as several launches
        (:mod:`repro.kernels.chunked`); ``None`` is one launch.

    Returns: (nblocks * block_r, nnb * bn) dense fp32 C.
    """
    assert a_values.shape[1:] == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn)
    in_specs, out_spec = _pair_specs(
        block_r, block_k, bn, nnb,
        pl.BlockSpec((1, block_k, bn),
                     lambda t, m, blks, js_, sl, ai: (sl[t], 0, 0)))
    return _stream_call(
        functools.partial(_spgemm_kernel_pairs, bn, block_r),
        (blocks, js, slots, a_idx), a_values, b_tiles,
        name="cluster_spgemm_pairs", slot_pos=2,
        chunk=chunk, in_specs=in_specs, out_spec=out_spec,
        out_shape=(nblocks * block_r, nnb * bn), interpret=interpret)


def _spgemm_kernel_pairs_resident(bn, block_r, meta_ref, blk_ref, j_ref,
                                  slot_ref, aidx_ref, a_ref, b_ref, c_hbm,
                                  o_ref, sem):
    t = pl.program_id(0)
    open_window(t, blk_ref, meta_ref, o_ref, _rows(c_hbm, block_r), sem)
    slot = slot_ref[t]

    @pl.when(slot > 0)
    def _acc():
        col = pl.multiple_of(j_ref[t] * bn, bn)
        _mxu_acc(a_ref[0], b_ref[slot], o_ref, col, bn)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nblocks", "nnb", "chunk", "interpret"))
def cluster_spgemm_pairs_resident(blocks: jax.Array, js: jax.Array,
                                  slots: jax.Array, a_idx: jax.Array,
                                  a_values: jax.Array, b_tiles: jax.Array,
                                  *, block_r: int, block_k: int, bn: int,
                                  nblocks: int, nnb: int,
                                  chunk: int | None = None,
                                  interpret: bool = False) -> jax.Array:
    """Same contract as :func:`cluster_spgemm_pairs`, with the whole B
    tile store pinned in VMEM (one HBM fetch per launch)."""
    assert a_values.shape[1:] == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn)
    tile_cap = b_tiles.shape[0]
    in_specs, out_spec = _pair_specs(
        block_r, block_k, bn, nnb,
        pl.BlockSpec((tile_cap, block_k, bn),
                     lambda t, m, blks, js_, sl, ai: (0, 0, 0)))
    return _stream_call(
        functools.partial(_spgemm_kernel_pairs_resident, bn, block_r),
        (blocks, js, slots, a_idx), a_values, b_tiles,
        name="cluster_spgemm_pairs_resident", slot_pos=2,
        chunk=chunk, in_specs=in_specs, out_spec=out_spec,
        out_shape=(nblocks * block_r, nnb * bn), interpret=interpret)


def _prefetch_tiles(t, slot_ref, b_hbm, b_buf, sem):
    """Two-slot B tile pipeline: the tile of step t+1 is in flight while
    step t contracts. Returns step t's tile, waited on."""
    nt = pl.num_programs(0)

    def _tile_dma(pos, buf):
        return pltpu.make_async_copy(b_hbm.at[slot_ref[pos]],
                                     b_buf.at[buf], sem.at[buf])

    @pl.when(t == 0)
    def _warm():                      # prime the pipeline
        _tile_dma(0, 0).start()

    @pl.when(t + 1 < nt)
    def _ahead():                     # overlap: fetch t+1 while t computes
        _tile_dma(t + 1, (t + 1) % 2).start()

    _tile_dma(t, t % 2).wait()
    return b_buf[t % 2]


def _spgemm_kernel_pairs_db(bn, block_r, meta_ref, blk_ref, j_ref, slot_ref,
                            aidx_ref, a_ref, b_hbm, c_hbm, o_ref, b_buf,
                            sem, carry_sem):
    t = pl.program_id(0)
    tile = _prefetch_tiles(t, slot_ref, b_hbm, b_buf, sem)
    open_window(t, blk_ref, meta_ref, o_ref, _rows(c_hbm, block_r),
                carry_sem)

    @pl.when(slot_ref[t] > 0)
    def _acc():
        col = pl.multiple_of(j_ref[t] * bn, bn)
        _mxu_acc(a_ref[0], tile, o_ref, col, bn)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nblocks", "nnb", "chunk", "interpret"))
def cluster_spgemm_pairs_db(blocks: jax.Array, js: jax.Array,
                            slots: jax.Array, a_idx: jax.Array,
                            a_values: jax.Array, b_tiles: jax.Array,
                            *, block_r: int, block_k: int, bn: int,
                            nblocks: int, nnb: int,
                            chunk: int | None = None,
                            interpret: bool = False) -> jax.Array:
    """Streamed variant with manual double-buffered tile prefetch: B stays
    in HBM (``ANY`` space) and each grid step DMAs the *next* step's tile
    into the other half of a two-slot VMEM scratch while contracting the
    current one — hiding the tile fetch latency the BlockSpec-driven
    streamed variant serializes. Same contract as
    :func:`cluster_spgemm_pairs`.
    """
    assert a_values.shape[1:] == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn)
    in_specs, out_spec = _pair_specs(block_r, block_k, bn, nnb,
                                     pl.BlockSpec(memory_space=pl.ANY))
    return _stream_call(
        functools.partial(_spgemm_kernel_pairs_db, bn, block_r),
        (blocks, js, slots, a_idx), a_values, b_tiles,
        name="cluster_spgemm_pairs_db", slot_pos=2,
        chunk=chunk, in_specs=in_specs, out_spec=out_spec,
        out_shape=(nblocks * block_r, nnb * bn),
        scratch=(pltpu.VMEM((2, block_k, bn), b_tiles.dtype),
                 pltpu.SemaphoreType.DMA((2,))),
        interpret=interpret)


# ---------------------------------------------------------------------------
# v3: B-fetch-deduping revisit order (windowed C accumulator)
# ---------------------------------------------------------------------------


def _spgemm_kernel_pairs_window(bn, block_r, window_blocks, meta_ref,
                                win_ref, blk_ref, j_ref, slot_ref, aidx_ref,
                                a_ref, b_ref, c_hbm, o_ref, sem):
    t = pl.program_id(0)
    # one zero-fill per *window* of strips
    open_window(t, win_ref, meta_ref, o_ref,
                _rows(c_hbm, window_blocks * block_r), sem)

    @pl.when(slot_ref[t] > 0)        # sentinels / tail pads: no MXU issue
    def _acc():
        col = pl.multiple_of(j_ref[t] * bn, bn)
        row = pl.multiple_of(
            (blk_ref[t] - win_ref[t] * window_blocks) * block_r, block_r)
        prod = jnp.dot(a_ref[0], b_ref[0].astype(jnp.float32),
                       preferred_element_type=jnp.float32,
                       precision=_FP32)
        o_ref[pl.ds(row, block_r), pl.ds(col, bn)] += prod.astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nblocks", "nnb", "window_blocks", "chunk",
    "interpret"))
def cluster_spgemm_pairs_window(wins: jax.Array, blocks: jax.Array,
                                js: jax.Array, slots: jax.Array,
                                a_idx: jax.Array, a_values: jax.Array,
                                b_tiles: jax.Array, *, block_r: int,
                                block_k: int, bn: int, nblocks: int,
                                nnb: int, window_blocks: int,
                                chunk: int | None = None,
                                interpret: bool = False) -> jax.Array:
    """C = A_bcc @ B_tiled over a revisit-ordered pair stream.

    Same contract as :func:`cluster_spgemm_pairs` except the stream is
    ordered by :func:`repro.core.formats.revisit_pair_stream` — triples
    sharing a B tile are adjacent across blocks, so the streamed-B DMA is
    elided down to one fetch per tile per window — and the C output
    window covers ``window_blocks`` consecutive block strips
    (``wins[t] = blocks[t] // window_blocks`` must be non-decreasing; the
    window is zero-initialized on entry, so every strip it owns reads
    back exactly its accumulated value, dead strips included).

    Returns: (nblocks * block_r, nnb * bn) dense fp32 C.
    """
    assert a_values.shape[1:] == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn)
    nwin = (nblocks + window_blocks - 1) // window_blocks
    in_specs = [
        pl.BlockSpec((1, block_r, block_k),
                     lambda t, m, w, blks, js_, sl, ai: (ai[t], 0, 0)),
        pl.BlockSpec((1, block_k, bn),
                     lambda t, m, w, blks, js_, sl, ai: (sl[t], 0, 0)),
    ]
    out_spec = pl.BlockSpec((window_blocks * block_r, nnb * bn),
                            lambda t, m, w, blks, js_, sl, ai: (w[t], 0))
    out = _stream_call(
        functools.partial(_spgemm_kernel_pairs_window, bn, block_r,
                          window_blocks),
        (wins, blocks, js, slots, a_idx), a_values, b_tiles,
        name="cluster_spgemm_pairs_window", slot_pos=3,
        chunk=chunk, in_specs=in_specs, out_spec=out_spec,
        out_shape=(nwin * window_blocks * block_r, nnb * bn),
        interpret=interpret)
    return out[: nblocks * block_r]


# ---------------------------------------------------------------------------
# v3: multi-core sharded pair stream (shard_map over a 1-D core mesh)
# ---------------------------------------------------------------------------


def _stack_shard_streams(shard_pairs) -> tuple:
    """Pad every shard's sub-stream to the longest one (zero-slot repeats
    of its last pair — the live_pair_stream tail convention) and stack
    into (S, T_max) arrays so shard_map sees a rectangular layout."""
    t_max = max(p[0].shape[0] for p in shard_pairs)
    cols = [[], [], [], []]
    for sb, sj, ss, sa in shard_pairs:
        pad = t_max - sb.shape[0]
        cols[0].append(np.concatenate([sb, np.repeat(sb[-1], pad)]))
        cols[1].append(np.concatenate([sj, np.repeat(sj[-1], pad)]))
        cols[2].append(np.concatenate([ss, np.zeros(pad, ss.dtype)]))
        cols[3].append(np.concatenate([sa, np.repeat(sa[-1], pad)]))
    return tuple(np.stack(c).astype(np.int32) for c in cols)


def _shard_local_call(blocks, js, slots, a_idx, a_values, b_tiles, *,
                      start, block_r, block_k, bn, max_blocks, nnb,
                      window_blocks, resident, double_buffer, chunk,
                      interpret):
    """One core's kernel launch: localize block ids to the shard's range
    and run the flat pair grid (windowed when revisit-ordered)."""
    local = blocks - start
    if window_blocks is None:
        if resident:
            kernel = cluster_spgemm_pairs_resident
        elif double_buffer:
            kernel = cluster_spgemm_pairs_db
        else:
            kernel = cluster_spgemm_pairs
        return kernel(
            local, js, slots, a_idx, a_values, b_tiles,
            block_r=block_r, block_k=block_k, bn=bn,
            nblocks=max_blocks, nnb=nnb, chunk=chunk, interpret=interpret)
    wins = local // window_blocks
    return cluster_spgemm_pairs_window(
        wins, local, js, slots, a_idx, a_values, b_tiles,
        block_r=block_r, block_k=block_k, bn=bn, nblocks=max_blocks,
        nnb=nnb, window_blocks=window_blocks, chunk=chunk,
        interpret=interpret)


def cluster_spgemm_pairs_sharded(shard_pairs, block_ranges,
                                 a_values: jax.Array, b_tiles: jax.Array,
                                 *, block_r: int, block_k: int, bn: int,
                                 nblocks: int, nnb: int,
                                 window_blocks: int | None = None,
                                 resident: bool = False,
                                 double_buffer: bool = False,
                                 chunk: int | None = None,
                                 interpret: bool = False,
                                 use_shard_map: bool | None = None
                                 ) -> jax.Array:
    """C = A_bcc @ B_tiled with the pair stream sharded across TPU cores.

    Args:
      shard_pairs: per-core ``(blocks, js, slots, a_idx)`` sub-streams
        from :func:`repro.core.formats.partition_pair_stream` (each
        optionally revisit-ordered relative to its own first block —
        pass ``window_blocks`` iff so).
      block_ranges: (S, 2) contiguous ``[start, end)`` block ranges of
        the same partition — shard ``i`` owns C rows
        ``start_i*block_r .. end_i*block_r``.
      a_values / b_tiles: the full (replicated) A slab array and B tile
        store — every core indexes them through its own sub-stream.
      window_blocks: the revisit window of each shard's sub-stream, or
        ``None`` for (block, s, j)-ordered shards.
      resident: pin B's tile store in each core's VMEM (only for
        unordered shards — the revisit order exists to dedup *streamed*
        tile fetches, which a resident store does not pay).
      double_buffer: run each core's streamed sub-stream through the
        two-slot manual-DMA prefetch kernel (unordered shards only;
        ignored when ``resident`` or ``window_blocks`` applies).
      chunk: most pairs per launch of each core's sub-stream (see
        :func:`cluster_spgemm_pairs`).
      use_shard_map: force the ``shard_map`` dispatch (needs one device
        per shard) or the serial loop; default auto — shard_map when the
        backend has enough devices and compilation is real (interpret
        mode runs the identical partition serially, so off-TPU tests
        exercise the same code path minus the mesh).

    Returns: (nblocks * block_r, nnb * bn) dense fp32 C — identical to
    the unsharded kernel on the unpartitioned stream (shards own
    disjoint row strips; each strip's accumulation order is unchanged).
    """
    ranges = np.asarray(block_ranges, dtype=np.int64)
    n_shards = len(shard_pairs)
    assert ranges.shape == (n_shards, 2)
    max_blocks = int((ranges[:, 1] - ranges[:, 0]).max())
    if use_shard_map is None:
        use_shard_map = (not interpret and n_shards > 1
                         and jax.device_count() >= n_shards)
    kw = dict(block_r=block_r, block_k=block_k, bn=bn,
              max_blocks=max_blocks, nnb=nnb,
              window_blocks=window_blocks, resident=resident,
              double_buffer=double_buffer, chunk=chunk, interpret=interpret)
    if not use_shard_map:
        # serial fallback: the same partition, one launch per shard
        outs = []
        for (start, end), pairs in zip(ranges, shard_pairs):
            sb, sj, ss, sa = to_device(*pairs)
            out = _shard_local_call(sb, sj, ss, sa, a_values, b_tiles,
                                    start=int(start), **kw)
            outs.append(out[: (int(end) - int(start)) * block_r])
        return jnp.concatenate(outs, axis=0)

    from repro.distributed.sharding import core_mesh
    from jax.sharding import PartitionSpec as P
    mesh = core_mesh(n_shards)
    blk, js_, sl, ai, starts = to_device(
        *_stack_shard_streams(shard_pairs), ranges[:, :1].astype(np.int32))

    def body(blk, js_, sl, ai, starts, a_values, b_tiles):
        out = _shard_local_call(blk[0], js_[0], sl[0], ai[0],
                                a_values, b_tiles,
                                start=starts[0, 0], **kw)
        return out[None]

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("cores"), P("cores"), P("cores"), P("cores"),
                  P("cores"), P(), P()),
        out_specs=P("cores"), check_vma=False)
    stacked = mapped(blk, js_, sl, ai, starts, a_values, b_tiles)
    # reassemble: shard i's first (end-start) block strips are its C rows
    outs = [stacked[i, : (int(e) - int(s)) * block_r]
            for i, (s, e) in enumerate(ranges)]
    return jnp.concatenate(outs, axis=0)


# ---------------------------------------------------------------------------
# v4: sparse-C output — compact live C windows on block exit
# ---------------------------------------------------------------------------


def _slab(c_hbm):
    """Window view of the aliased slab store: slab ``key``."""
    return lambda key: c_hbm.at[pl.ds(key, 1)]


def _spgemm_kernel_pairs_sparse(meta_ref, cw_ref, slot_ref, aidx_ref,
                                a_ref, b_ref, c_hbm, o_ref, sem):
    t = pl.program_id(0)
    # one zero-fill per live C window
    open_window(t, cw_ref, meta_ref, o_ref, _slab(c_hbm), sem)

    @pl.when(slot_ref[t] > 0)        # slab-0 sentinel / tail pads: no MXU
    def _acc():
        prod = jnp.dot(a_ref[0], b_ref[0].astype(jnp.float32),
                       preferred_element_type=jnp.float32,
                       precision=_FP32)
        o_ref[0] += prod.astype(o_ref.dtype)


def _sparse_specs(block_r, block_k, bn, b_spec):
    """A-slab spec, the given B spec, and the slab spec of the (meta,
    c_slots, slots, a_idx) prefetch layout."""
    a_spec = pl.BlockSpec((1, block_r, block_k),
                          lambda t, m, cw, sl, ai: (ai[t], 0, 0))
    out_spec = pl.BlockSpec((1, block_r, bn),
                            lambda t, m, cw, sl, ai: (cw[t], 0, 0))
    return [a_spec, b_spec], out_spec


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nslabs", "chunk", "interpret"))
def cluster_spgemm_pairs_sparse(c_slots: jax.Array, slots: jax.Array,
                                a_idx: jax.Array, a_values: jax.Array,
                                b_tiles: jax.Array, *, block_r: int,
                                block_k: int, bn: int, nslabs: int,
                                chunk: int | None = None,
                                interpret: bool = False) -> jax.Array:
    """Numeric phase of the sparse-C pipeline: accumulate each live
    ``(blk, j)`` C window in VMEM and write it back once as a packed
    :class:`repro.core.formats.CompactedC` slab.

    Args:
      c_slots: (T,) int32, non-decreasing — destination slab of each pair
        (``CompactedC.table[blk*nnb + j]``). The stream MUST be
        window-major (sorted by (blk, j), s ascending within a window —
        :func:`repro.kernels.ops.build_sparse_c_pairs`) so each output
        slab is visited contiguously: Pallas writes an output block back
        when its index changes, and revisiting it later would clobber.
        Slot 0 (the reserved zero slab) is visited by one leading
        sentinel pair so it initializes.
      slots: (T,) int32 — B tile slot per pair, 0 = no MXU issue (the
        sentinel and tail pads).
      a_idx: (T,) int32 — A stream index per pair.
      a_values / b_tiles / chunk: as in :func:`cluster_spgemm_pairs`.

    Returns: (nslabs, block_r, bn) fp32 slab store — ``CompactedC.slabs``.
    """
    assert a_values.shape[1:] == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn)
    in_specs, out_spec = _sparse_specs(
        block_r, block_k, bn,
        pl.BlockSpec((1, block_k, bn),
                     lambda t, m, cw, sl, ai: (sl[t], 0, 0)))
    return _stream_call(
        _spgemm_kernel_pairs_sparse, (c_slots, slots, a_idx), a_values,
        b_tiles, name="cluster_spgemm_pairs_sparse", slot_pos=1,
        chunk=chunk, in_specs=in_specs, out_spec=out_spec,
        out_shape=(nslabs, block_r, bn),
        interpret=interpret)


def _spgemm_kernel_pairs_sparse_db(meta_ref, cw_ref, slot_ref, aidx_ref,
                                   a_ref, b_hbm, c_hbm, o_ref, b_buf, sem,
                                   carry_sem):
    t = pl.program_id(0)
    tile = _prefetch_tiles(t, slot_ref, b_hbm, b_buf, sem)
    open_window(t, cw_ref, meta_ref, o_ref, _slab(c_hbm), carry_sem)

    @pl.when(slot_ref[t] > 0)
    def _acc():
        prod = jnp.dot(a_ref[0], tile.astype(jnp.float32),
                       preferred_element_type=jnp.float32,
                       precision=_FP32)
        o_ref[0] += prod.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_r", "block_k", "bn", "nslabs", "chunk", "interpret"))
def cluster_spgemm_pairs_sparse_db(c_slots: jax.Array, slots: jax.Array,
                                   a_idx: jax.Array, a_values: jax.Array,
                                   b_tiles: jax.Array, *, block_r: int,
                                   block_k: int, bn: int, nslabs: int,
                                   chunk: int | None = None,
                                   interpret: bool = False) -> jax.Array:
    """Sparse-C variant with manual double-buffered B tile prefetch: B
    stays in HBM (``ANY`` space) and step t+1's tile is in flight while
    step t contracts — :func:`cluster_spgemm_pairs_db`'s pipeline on the
    sparse-C output path. Same contract as
    :func:`cluster_spgemm_pairs_sparse`."""
    assert a_values.shape[1:] == (block_r, block_k)
    assert b_tiles.shape[1:] == (block_k, bn)
    in_specs, out_spec = _sparse_specs(block_r, block_k, bn,
                                       pl.BlockSpec(memory_space=pl.ANY))
    return _stream_call(
        _spgemm_kernel_pairs_sparse_db, (c_slots, slots, a_idx), a_values,
        b_tiles, name="cluster_spgemm_pairs_sparse_db", slot_pos=1,
        chunk=chunk, in_specs=in_specs, out_spec=out_spec,
        out_shape=(nslabs, block_r, bn),
        scratch=(pltpu.VMEM((2, block_k, bn), b_tiles.dtype),
                 pltpu.SemaphoreType.DMA((2,))),
        interpret=interpret)
