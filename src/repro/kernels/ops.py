"""Jit'd public wrappers around the Pallas kernels.

These adapt framework-level types (``core.formats.BCC``, GQA-shaped
attention tensors) to the kernel calling conventions, handle padding, and
select interpret mode automatically off-TPU so the same call sites run in
CI (CPU, interpret=True) and production (TPU, compiled).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import (BCC, BCCShape, CompactedC, HostCSR,
                                TiledCSR, bcc_layout, block_r_counts,
                                compacted_c_counters,
                                compacted_c_from_dense,
                                compacted_c_positions, compacted_c_table,
                                live_pair_counters, live_pair_stream,
                                mask_windows,
                                partition_pair_stream, scatter_map,
                                tiled_layout)
from repro.core.segment import expand_indptr, rank_in_segment
from repro.core.transfer import to_device, to_host
from repro.obs import metrics as obs_metrics
from repro.obs.trace import get_tracer
from repro.kernels.cluster_spgemm import (cluster_spgemm_pairs,
                                          cluster_spgemm_pairs_db,
                                          cluster_spgemm_pairs_resident,
                                          cluster_spgemm_pairs_sharded,
                                          cluster_spgemm_pairs_sparse,
                                          cluster_spgemm_pairs_sparse_db,
                                          cluster_spgemm_resident,
                                          cluster_spgemm_tiled)
from repro.kernels.cluster_spmm import cluster_spmm, cluster_spmm_compact
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_chunk import ssd_chunk_scan
from repro.resilience import faults as _faults

__all__ = ["on_tpu", "pallas_shard_count", "stream_chunk", "bcc_spmm",
           "bcc_compact_stream", "bcc_compact_stream_reference",
           "bcc_spmm_compact", "predict_c_window_density",
           "compact_grid_ok_ncols", "bcc_spgemm_tiled",
           "bcc_spgemm_sparse_c", "SpGEMMPattern", "select_block_r",
           "pack_spgemm_pattern", "int32_positions",
           "pack_spmm_stream", "flash_mha", "fused_ssd"]

# B's tile width on the serving path, and the row-block heights of A that
# select_block_r tries for a Sp×Sp pattern: the first is the one the
# route tests and the padded grid use
_BN = 128
_BLOCK_R_HEIGHTS = (8, 16, 32, 64, 128)

# VMEM budget for pinning TiledCSR's tile store on-chip (leave headroom for
# the A slab / C tile double buffers out of the 16 MiB core budget)
_RESIDENT_B_BUDGET = 8 * 2**20

# SMEM shared by the scalar-prefetched streams of one Pallas launch. The
# chip's compiler places every prefetched array whole in the TensorCore's
# SMEM, 1 MiB on a TPU v5e (its refusal when the streams overflow it:
# "Ran out of memory in memory space smem. Used 1.00M of 1.00M smem").
# Half of it is left to the compiler's own scalars; longer streams are
# launched in chunks of stream_chunk() steps.
_SMEM_STREAM_BUDGET = 2**19

# ceiling on the compacted kernels' C row-strip window (block_r × nnb·bn
# fp32, double-buffered by the pipeline): B matrices wide enough to blow
# it fall back to the per-tile padded grid, whose C window is one tile
_COMPACT_C_STRIP_BUDGET = 2 * 2**20

# what a Sp×Sp grid step costs beyond its bytes and flops, in bytes of HBM
# traffic (select_block_r)
_STEP_OVERHEAD_BYTES = 192 * 2**10

# row blocks per core the sharded route keeps at the least when it chooses
# A's row-block height: the partition cuts the stream at block
# boundaries, so a core with fewer, taller blocks balances worse
_SHARD_BLOCKS = 8

# predicted C window density (live (blk, j) windows / all windows) at or
# below which pack_spgemm_pattern routes through the sparse-C output tier:
# at 0.5 the compacted slab writes are at most half the dense strips'
# bytes, so the 2× C-bytes gate holds by construction on routed families
_SPARSE_C_DENSITY = 0.5


def _note_kernel_launch(variant: str, *, pairs=None, block_r=None,
                        block_k=None, bn=None, cc=None) -> None:
    """Account one Sp×Sp dispatch: the ``kernel_launches`` counter
    (labelled by variant) plus — only when the registry's opt-in
    ``device_emission`` flag is on, the counters are O(pairs) host work —
    the declared device traffic counters of the launch."""
    reg = obs_metrics.get_registry()
    reg.counter("kernel_launches", variant=variant).inc()
    if not reg.device_emission:
        return
    if pairs is not None:
        reg.emit_device_counters(
            live_pair_counters(pairs, block_r=block_r, block_k=block_k,
                               bn=bn), variant=variant)
    if cc is not None:
        reg.emit_device_counters(compacted_c_counters(cc), variant=variant)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_shard_count() -> int:
    """Cores the sharded pair-stream kernel fans out over: every local
    device on a TPU backend, 1 elsewhere (the CPU 'devices' are host
    threads — sharding the stream over them only adds dispatch overhead,
    and interpret-mode tests want the serial path's determinism)."""
    return jax.device_count() if on_tpu() else 1


def stream_chunk(n_streams: int) -> int:
    """Steps per launch of a kernel that scalar-prefetches ``n_streams``
    int32 streams: the most that fit :data:`_SMEM_STREAM_BUDGET`, a
    multiple of 8.

    >>> stream_chunk(4)
    32768
    """
    return max(8, _SMEM_STREAM_BUDGET // (4 * n_streams) // 8 * 8)


def _pad_cols(b: jax.Array, multiple: int) -> jax.Array:
    n = b.shape[-1]
    pad = (-n) % multiple
    if pad:
        b = jnp.pad(b, ((0, 0), (0, pad)))
    return b


def bcc_spmm(a: BCC, b: jax.Array, *, bn: int = 128,
             interpret: bool | None = None) -> jax.Array:
    """C = A_bcc @ B via the padded-grid cluster kernel. Returns (nrows, N)."""
    if interpret is None:
        interpret = not on_tpu()
    k_needed = ((a.ncols + a.block_k - 1) // a.block_k) * a.block_k
    if b.shape[0] < k_needed:
        b = jnp.pad(b, ((0, k_needed - b.shape[0]), (0, 0)))
    n0 = b.shape[1]
    bn_eff = min(bn, max(8, n0))
    b = _pad_cols(b, bn_eff)
    out = cluster_spmm(a.tile_ids, a.values, b,
                       block_r=a.block_r, block_k=a.block_k,
                       tiles_per_block=a.tiles_per_block, bn=bn_eff,
                       interpret=interpret)
    return out[: a.nrows, : n0]


def bcc_compact_stream(a: BCC, *, cover_all_blocks: bool = False
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side: squeeze the padded (block, tile) lattice to live tiles.

    Returns (block_ids, tile_ids, values) sorted by block — the input of
    :func:`bcc_spmm_compact`. Tail-padded (repeating the last block with zero
    slabs) to a multiple of 8 steps. ``cover_all_blocks=True`` additionally
    emits one zero-slab step for every block with *no* live tiles, so a
    compact-grid kernel visits (and zero-initializes) every output strip —
    required by the Sp×Sp kernel, whose C is dense over all row blocks.

    Vectorized: the live-slot mask is one broadcast compare against
    ``ntiles``; the squeeze is one ``flatnonzero`` + fancy gather.
    Identical stream to :func:`bcc_compact_stream_reference`.
    """
    ntiles, tile_ids, values = to_host(a.ntiles, a.tile_ids, a.values)
    keep, live = _compact_keep(ntiles, a.tiles_per_block, cover_all_blocks)
    vals = values[keep]
    vals[live:] = 0.0
    # slabs of empty blocks (cover_all_blocks) are all-zero by construction
    # in the padded lattice, so their steps contribute nothing
    return ((keep // a.tiles_per_block).astype(np.int32),
            tile_ids[keep].astype(np.int32), vals)


def _compact_keep(ntiles: np.ndarray, tpb: int, cover_all_blocks: bool
                  ) -> tuple[np.ndarray, int]:
    """``(keep, live)``: the value-lattice slot of each step of the compact
    stream, block-sorted, and the count of its steps that are not tail
    padding (the padding repeats the last slot, to a multiple of 8)."""
    eff = np.maximum(ntiles, 1) if cover_all_blocks else ntiles
    live_mask = np.arange(tpb, dtype=np.int64)[None, :] < eff[:, None]
    keep = np.flatnonzero(live_mask.ravel())
    if keep.size == 0:   # fully empty matrix: single zero step
        keep = np.zeros(1, dtype=np.int64)
    live = keep.shape[0]
    pad = (-live) % 8
    return np.concatenate([keep, np.full(pad, keep[-1], np.int64)]), live


def bcc_compact_stream_reference(a: BCC, *, cover_all_blocks: bool = False
                                 ) -> tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """Loop reference for :func:`bcc_compact_stream` (test oracle)."""
    ntiles = np.asarray(a.ntiles)
    tpb = a.tiles_per_block
    tile_ids = np.asarray(a.tile_ids)
    values = np.asarray(a.values)
    keep = []
    blocks = []
    for blk in range(ntiles.shape[0]):
        n = int(ntiles[blk])
        if cover_all_blocks:
            n = max(n, 1)
        for t in range(n):
            keep.append(blk * tpb + t)
            blocks.append(blk)
    if not keep:   # fully empty matrix: single zero step
        keep, blocks = [0], [0]
    live = len(keep)
    pad = (-live) % 8
    keep = np.asarray(keep + [keep[-1]] * pad)
    block_ids = np.asarray(blocks + [blocks[-1]] * pad, dtype=np.int32)
    vals = values[keep]
    if pad:
        vals[live:] = 0.0
    return block_ids, tile_ids[keep].astype(np.int32), vals


def bcc_spmm_compact(a: BCC | BCCShape, b: jax.Array, *, bn: int = 128,
                     interpret: bool | None = None,
                     stream: tuple | None = None) -> jax.Array:
    """C = A_bcc @ B via the compact-stream kernel (no padding compute).

    ``stream`` is A's compact stream (:func:`bcc_compact_stream`, or
    :func:`pack_spmm_stream` for a :class:`BCCShape` ``a``); a stream
    already on the device launches as it is, with no ``upload``."""
    if interpret is None:
        interpret = not on_tpu()
    if stream is None:
        # cover_all_blocks: a block with no live tiles must still appear
        # once so the compact-grid kernel zero-initializes its C strip
        stream = bcc_compact_stream(a, cover_all_blocks=True)
    if not all(isinstance(s, jax.Array) for s in stream):
        stream = to_device(*stream)
    block_ids, tile_ids, values = stream
    k_needed = ((a.ncols + a.block_k - 1) // a.block_k) * a.block_k
    if b.shape[0] < k_needed:
        b = jnp.pad(b, ((0, k_needed - b.shape[0]), (0, 0)))
    n0 = b.shape[1]
    bn_eff = min(bn, max(8, n0))
    b = _pad_cols(b, bn_eff)
    nblocks = (a.nrows + a.block_r - 1) // a.block_r
    with get_tracer().span("kernel_variant", variant="spmm_compact"):
        out = cluster_spmm_compact(block_ids, tile_ids, values, b,
                                   block_r=a.block_r, block_k=a.block_k,
                                   nblocks=nblocks, bn=bn_eff,
                                   chunk=stream_chunk(2),
                                   interpret=interpret)
    _note_kernel_launch("spmm_compact")
    return out[: a.nrows, : n0]


def compact_grid_ok_ncols(ncols: int, *, block_r: int = _BLOCK_R_HEIGHTS[0],
                          bn: int = _BN) -> bool:
    """Whether the live-pair compacted grid applies to a B of ``ncols``
    columns: its C output window is a whole ``(block_r, nnb*bn)`` row
    strip, so B wide enough to blow the strip budget takes the padded
    per-tile grid. :func:`pack_spgemm_pattern` routes on it, and the
    cost model and the chain planner read it before any packing (one
    source of truth for the strip-budget rule)."""
    nnb = (max(ncols, 1) + bn - 1) // bn
    return block_r * nnb * bn * 4 <= _COMPACT_C_STRIP_BUDGET


def _live_pairs(stream, ntiles: np.ndarray, table: np.ndarray, *, nnb: int,
                nblocks: int, window_live: np.ndarray | None = None) -> tuple:
    """Intersect A's compact stream with B's tile ``table`` into the
    live-pair compacted grid (the pair kernels' input), on the host.

    Synthetic stream steps — the ``cover_all_blocks`` zero slabs of
    empty blocks (``ntiles`` is A's live tile count per block) and the
    tail padding — are masked out of the pair expansion (their slabs are
    all-zero; the pair grid re-covers their blocks with its own
    zero-slot sentinels). ``window_live`` keeps only the pairs of those
    C windows (a masked product's).
    """
    block_ids, tile_ids = np.asarray(stream[0]), np.asarray(stream[1])
    step_live = rank_in_segment(block_ids.astype(np.int64)) \
        < ntiles[block_ids]
    return live_pair_stream(block_ids, tile_ids, table, nnb=nnb,
                            nblocks=nblocks, step_live=step_live,
                            window_live=window_live)


def predict_c_window_density(pairs, *, nblocks: int, nnb: int) -> float:
    """Predicted density of C's ``(block_r, bn)`` window lattice: distinct
    live ``(blk, j)`` windows over all ``nblocks × nnb`` windows — known
    *before* the numeric phase from the live-pair stream alone (a window
    with no live pair is provably zero). :func:`pack_spgemm_pattern`
    routes dense-strip vs sparse-C on it: the sparse tier's C bytes are
    exactly ``density`` of the dense strips'."""
    blocks, js, slots, _ = (np.asarray(p) for p in pairs)
    live = slots > 0
    key = blocks[live].astype(np.int64) * nnb + js[live].astype(np.int64)
    return np.unique(key).size / max(nblocks * nnb, 1)


def _sparse_c_pairs(pairs, *, nblocks: int, nnb: int, pad_to: int = 8
                    ) -> tuple:
    """Re-sort the live-pair stream window-major for the sparse-C kernels
    and tag each pair with its destination
    :class:`repro.core.formats.CompactedC` slab, on the host.

    The dense kernels need (block, s, j) order — one C *strip* per block,
    visited once. The sparse-C kernels' output block is one ``(blk, j)``
    *window*, so the stream re-sorts by (blk, j, s): every slab is
    visited contiguously (Pallas writes an output block back when its
    index changes; a second visit would clobber), and within a window pairs
    stay s-ascending — the same per-element fp32 accumulation order as
    the dense kernels, hence bit-identical values.

    Zero-slot sentinels and tail pads of the input stream are dropped
    (dead windows need no zero-init — the reserved zero slab covers them
    through the table); one leading sentinel pair (slab 0, B slot 0) is
    prepended so the reserved slab zero-initializes, and the tail is
    re-padded to ``pad_to`` with no-MXU repeats of the last window.

    Returns ``(c_slots, slots, a_idx, table, nslabs)`` — the first three
    are the kernel's scalar-prefetched stream, ``table``/``nslabs`` the
    CompactedC lookup table and slab count (live windows + the zero
    slab).
    """
    table, nlive = compacted_c_table(pairs, nblocks=nblocks, nnb=nnb)
    blocks, js, slots, a_idx = (np.asarray(p) for p in pairs)
    live = slots > 0
    bl = blocks[live].astype(np.int64)
    jl = js[live].astype(np.int64)
    sl = slots[live]
    al = a_idx[live]
    order = np.lexsort((al, jl, bl))
    bl, jl, sl, al = bl[order], jl[order], sl[order], al[order]
    c_slots = table[bl * nnb + jl].astype(np.int64)
    anchor = int(al[0]) if al.size else 0
    c_slots = np.concatenate([[0], c_slots])
    sl = np.concatenate([[0], sl.astype(np.int64)])
    al = np.concatenate([[anchor], al.astype(np.int64)])
    pad = (-c_slots.size) % pad_to
    if pad:
        c_slots = np.concatenate([c_slots, np.repeat(c_slots[-1], pad)])
        sl = np.concatenate([sl, np.zeros(pad, np.int64)])
        al = np.concatenate([al, np.repeat(al[-1], pad)])
    return (c_slots.astype(np.int32), sl.astype(np.int32),
            al.astype(np.int32), table, nlive + 1)


def _sparse_c_kernel(pattern: "SpGEMMPattern", values: jax.Array,
                     tiled: TiledCSR) -> CompactedC:
    """The sparse-C product with the kernel epilogue: the pattern's
    sparse-C kernel accumulates each live C window in VMEM and its
    output BlockSpec scatters the window straight into the slab store."""
    a = pattern.a
    c_slots, slots, a_idx, table, nslabs = pattern.sparse_pairs
    values, c_slots, slots, a_idx, table = to_device(
        values, c_slots, slots, a_idx, table)
    with get_tracer().span("kernel_variant", variant="sparse_c",
                           epilogue="kernel", block_r=a.block_r,
                           **_mask_attrs(pattern)):
        slabs = pattern.kernel(c_slots, slots, a_idx, values, tiled.tiles,
                               block_r=a.block_r, block_k=a.block_k,
                               bn=tiled.bn, nslabs=int(nslabs),
                               chunk=stream_chunk(3), interpret=not on_tpu())
    out = CompactedC(slabs=slabs, table=table,
                     nrows=a.nrows, ncols=tiled.ncols,
                     block_r=a.block_r, bn=tiled.bn)
    _note_kernel_launch("sparse_c", cc=out)
    return out


def _mask_attrs(pattern: "SpGEMMPattern") -> dict:
    """The ``kernel_variant`` span's account of a masked pattern's pairs:
    those launched and those of the unmasked stream at the same
    height."""
    if pattern.mask_pairs is None:
        return {}
    kept, unmasked = pattern.mask_pairs
    return {"masked": True, "pairs": kept, "pairs_unmasked": unmasked}


def _sparse_c_xla(pattern: "SpGEMMPattern", values: jax.Array,
                  tiled: TiledCSR) -> CompactedC:
    """The sparse-C product with the XLA epilogue: the dense strips of
    the pattern's live pairs on the streamed compacted grid (through
    :func:`bcc_spgemm_tiled`), then an XLA segment-compaction gather of
    the live windows (:func:`repro.core.formats.compacted_c_from_dense`)
    — the same table, bit-identical slabs."""
    a = pattern.a
    dense = bcc_spgemm_tiled(
        dataclasses.replace(pattern, route="streamed",
                            kernel=cluster_spgemm_pairs), values, tiled)
    return compacted_c_from_dense(dense, pattern.sparse_pairs[3],
                                  nrows=a.nrows, ncols=tiled.ncols,
                                  block_r=a.block_r, bn=tiled.bn)


def bcc_spgemm_sparse_c(pattern: "SpGEMMPattern", values: jax.Array,
                        tiled: TiledCSR) -> CompactedC:
    """C = A @ B on one value set of a pattern packed for the sparse-C
    output tier (route ``sparse_c``): the numeric phase accumulates each
    live C window in VMEM like the dense-strip kernels but writes back
    *only* the live windows, as packed
    :class:`repro.core.formats.CompactedC` slabs — C bytes to HBM scale
    with nnz(C)'s window footprint, not ``rows × nnb·bn``.

    The compaction runs in the kernel on a TPU and as an XLA gather of
    the dense strips elsewhere (the same table, bit-identical slabs).
    """
    _faults.maybe_fault("kernel_launch")
    if on_tpu():
        return _sparse_c_kernel(pattern, values, tiled)
    return _sparse_c_xla(pattern, values, tiled)


def bcc_spgemm_tiled(pattern: "SpGEMMPattern", values: jax.Array,
                     tiled: TiledCSR) -> jax.Array:
    """C = A @ B on one value set of a packed pattern (``values`` and
    ``tiled`` from :meth:`SpGEMMPattern.fill`), launched on the route
    :func:`pack_spgemm_pattern` recorded. Returns the dense ``(nrows,
    ncols)`` product (fp32 — bf16 B tiles are upcast at the MXU input,
    accumulation stays fp32); the ``sparse_c`` route densifies its
    :class:`repro.core.formats.CompactedC` on the way out."""
    _faults.maybe_fault("kernel_launch")
    p, a = pattern, pattern.a
    if p.route == "sparse_c":
        return _sparse_c_kernel(p, values, tiled).to_dense()
    tracer = get_tracer()
    kw = dict(block_r=a.block_r, block_k=a.block_k, bn=tiled.bn,
              nblocks=(a.nrows + a.block_r - 1) // a.block_r,
              nnb=tiled.nnb, interpret=not on_tpu())
    pair_kw = dict(pairs=p.pairs, block_r=a.block_r, block_k=a.block_k,
                   bn=tiled.bn)
    # the pattern and the fill hold every operand on the device already:
    # each to_device below moves nothing, its upload span reads 0 bytes
    if p.route == "padded":
        block_ids, tile_ids, values = to_device(*p.stream_ids, values)
        with tracer.span("kernel_variant", variant="padded",
                         block_r=a.block_r,
                         resident=p.kernel is cluster_spgemm_resident):
            out = p.kernel(block_ids, tile_ids, tiled.table, values,
                           tiled.tiles, **kw)
        _note_kernel_launch("padded")
    elif p.route == "sharded":
        ranges, shard_pairs = p.shards
        values, = to_device(values)
        with tracer.span("kernel_variant", variant="sharded",
                         block_r=a.block_r, shards=len(shard_pairs)):
            out = cluster_spgemm_pairs_sharded(
                shard_pairs, ranges, values, tiled.tiles, kernel=p.kernel,
                chunk=stream_chunk(4), **kw)
        _note_kernel_launch("sharded", **pair_kw)
    else:
        values, *pairs = to_device(values, *p.pairs)
        with tracer.span("kernel_variant", variant=p.route,
                         block_r=a.block_r, **_mask_attrs(p)):
            out = p.kernel(*pairs, values, tiled.tiles,
                           chunk=stream_chunk(4), **kw)
        _note_kernel_launch(p.route, **pair_kw)
    return out[: a.nrows, : tiled.ncols]


def _compact_layout(h: HostCSR, block_r: int, block_k: int) -> tuple:
    """The value-free part of ``bcc_compact_stream(bcc_from_host(h),
    cover_all_blocks=True)``, on the host: ``(stream_ids, ntiles, pos,
    values_shape)``, where ``stream_ids`` is ``(block_ids, tile_ids)``
    and ``pos[i]`` is the flat index of nonzero ``i`` in the stream's
    ``values_shape`` slabs."""
    tile_ids, ntiles, tpb, pos = bcc_layout(h, block_r, block_k)
    keep, live = _compact_keep(ntiles, tpb, cover_all_blocks=True)
    stream_ids = ((keep // tpb).astype(np.int32),
                  tile_ids[keep].astype(np.int32))
    # the lattice position → its step of the compact stream
    slab = block_r * block_k
    step_of = np.zeros(ntiles.shape[0] * tpb, dtype=np.int64)
    step_of[keep[:live]] = np.arange(live)
    pos = step_of[pos // slab] * slab + pos % slab
    return stream_ids, ntiles, pos, (keep.shape[0], block_r, block_k)


def pack_spmm_stream(h: HostCSR, *, block_r: int = 8, block_k: int = 128
                     ) -> tuple[BCCShape, tuple]:
    """A's compact stream for :func:`bcc_spmm_compact`, built on the host
    from its layout and ``data`` and uploaded once: ``(shape, (block_ids,
    tile_ids, values))``, the arrays on the device and the same as
    ``bcc_compact_stream(bcc_from_host(h), cover_all_blocks=True)``, with
    no padded value lattice built, uploaded or read back."""
    stream_ids, _, pos, values_shape = _compact_layout(h, block_r, block_k)
    values = np.zeros(values_shape, dtype=np.float32)
    values.reshape(-1)[pos] = h.data
    return (BCCShape(h.nrows, h.ncols, block_r, block_k),
            to_device(*stream_ids, values))


@dataclasses.dataclass(frozen=True)
class SpGEMMPattern:
    """The Sp×Sp operands packed from their patterns alone, on the device,
    and the route that launches them.

    Everything a launch reads except two value arrays is a function of
    the patterns: A's blocking and compact stream ids, B's tile table,
    the live pairs, the shard partition, the sparse-C window-major stream
    and the route itself. The two value arrays, A's stream slabs and B's
    tile store, are zeros with the operands' values at fixed positions;
    ``a_map``/``b_map`` (``(src, dst)`` of
    :func:`repro.core.formats.scatter_map`) hold those positions, with
    ``src`` indexing the ``data`` of the operands as they were sent
    (before the plan's permutation). :meth:`fill` builds both arrays from
    a value set on the device, and :meth:`run` launches the kernel on
    them: the same slabs and tiles, in the same stream order, as a full
    :func:`bcc_from_host`/:func:`tiled_csr_from_host` pack.

    ``route`` names the launch, as the ``kernel_variant`` span and the
    ``kernel_launches`` counter name it: ``padded`` (the per-tile
    ``(nnb, S)`` grid), ``resident``, ``streamed`` or ``streamed_db``
    (the live-pair grid with B pinned in VMEM, streamed, or streamed
    behind a two-slot prefetch), ``sharded`` (the live-pair grid split
    over the cores) or ``sparse_c`` (the window-major sparse-C grid).
    ``kernel`` is the Pallas kernel the route launches (each core's, for
    ``sharded``).

    A pattern packed with a mask (``sparse_c`` only) also holds
    ``mask_pos``, where each nonzero of the mask, in the order it was
    sent, sits in the flattened sparse-C slabs, and ``mask_pairs``, the
    lengths of its pruned live-pair stream and of the unmasked one at
    the same height.
    """

    a: BCCShape
    b_shape: tuple                 # (nrows, ncols, block_k, bn) of B
    table: jax.Array               # B's tile table
    stream_ids: tuple              # (block_ids, tile_ids)
    route: str
    kernel: Callable
    pairs: tuple | None            # the live pairs; None on padded
    shards: tuple | None           # (ranges, per-core pairs) on sharded
    sparse_pairs: tuple | None     # (c_slots, slots, a_idx, table,
                                   # nslabs) on sparse_c
    a_map: tuple
    b_map: tuple
    values_shape: tuple
    tiles_shape: tuple
    tiles_dtype: object
    mask_pos: jax.Array | None = None
    mask_pairs: tuple | None = None

    def fill(self, a_data, b_data=None) -> tuple[jax.Array, TiledCSR]:
        """A's stream values and B's tiles for one value set, from the
        operands' ``data``; ``b_data=None`` takes B's values from
        ``a_data`` (the squared product, B = A)."""
        up = to_device(a_data) if b_data is None else to_device(a_data,
                                                               b_data)
        values, tiles = _fill_values(
            up[0], up[-1], *self.a_map, *self.b_map,
            values_shape=self.values_shape, tiles_shape=self.tiles_shape,
            tiles_dtype=self.tiles_dtype)
        if get_tracer().enabled:
            # like an upload, the fill's span closes on the device's work
            jax.block_until_ready((values, tiles))
        nrows, ncols, block_k, bn = self.b_shape
        return values, TiledCSR(tiles=tiles, table=self.table, nrows=nrows,
                                ncols=ncols, block_k=block_k, bn=bn)

    def run(self, values: jax.Array, tiled: TiledCSR) -> jax.Array:
        """C = A @ B, dense, on one value set that :meth:`fill` built."""
        return bcc_spgemm_tiled(self, values, tiled)

    def run_sparse(self, values: jax.Array, tiled: TiledCSR) -> CompactedC:
        """C = A @ B on one value set, left in the sparse-C output tier
        (:func:`bcc_spgemm_sparse_c`); the pattern must have been packed
        with ``sparse_out=True``."""
        if self.route != "sparse_c":
            raise ValueError("pattern packed without its sparse-C stream")
        return bcc_spgemm_sparse_c(self, values, tiled)


@functools.partial(jax.jit, static_argnames=("values_shape", "tiles_shape",
                                             "tiles_dtype"))
def _fill_values(a_data, b_data, a_src, a_dst, b_src, b_dst, *,
                 values_shape, tiles_shape, tiles_dtype):
    def scatter(data, src, dst, shape, dtype):
        flat = jnp.zeros(math.prod(shape), dtype).at[dst].set(
            data[src].astype(dtype), indices_are_sorted=True,
            unique_indices=True)
        return flat.reshape(shape)
    return (scatter(a_data, a_src, a_dst, values_shape, jnp.float32),
            scatter(b_data, b_src, b_dst, tiles_shape, tiles_dtype))


def _value_map(pos: np.ndarray, src: np.ndarray | None, size: int) -> tuple:
    """:func:`scatter_map` of ``pos``, its sources composed with ``src``
    (the operand's permutation, ``None`` for none), both int32."""
    if size >= 2**31:
        raise ValueError(f"value array of {size} entries is too large for "
                         "int32 positions")
    take, dst = scatter_map(pos)
    if src is not None:
        take = np.asarray(src)[take]
    return take.astype(np.int32), dst.astype(np.int32)


def select_block_r(counts: dict, *, route: str, nrows: int, block_k: int,
                   nnb: int, bn: int = _BN, b_itemsize: int = 4,
                   shards: int = 1) -> int:
    """Row-block height of the Sp×Sp operands: the height of ``counts``
    (:func:`repro.core.formats.block_r_counts`) at which a launch on
    ``route`` costs least, its bytes, its MXU time and its grid steps all
    counted in bytes of HBM traffic.

    A taller block contracts each B tile it fetches against more rows of
    A, so a pattern whose row blocks share k-tiles runs fewer pairs and
    streams fewer B tiles. A pattern whose rows share none buys only
    zero-filled A slabs. The bytes of a launch at height ``r``, and its
    refill, as the kernels move them:

    * A's slabs, ``steps·r·block_k·4``: written by the refill, read by the
      kernel once per stream step on the dense-strip routes (a step's
      pairs are adjacent) and once per pair on ``sparse_c`` (its stream
      is window-major);
    * B's tiles, ``pairs·block_k·bn·b_itemsize``: one DMA per pair;
    * C: the dense row strips, ``nblocks·r·nnb·bn·4``, or on ``sparse_c``
      the live windows, ``windows·r·bn·4``;
    * the MXU's time, counted as the bytes moved in that time: a pair's
      ``r·block_k·bn·2`` flops run as six bf16 passes at ``HIGHEST``
      precision, at 197 TFLOP/s against 819 GB/s on a TPU v5e, are
      ``r·block_k·bn/20`` bytes;
    * ``_STEP_OVERHEAD_BYTES`` per pair, 192 KiB: 0.24 µs at 819 GB/s,
      what a step costs beyond its bytes and flops (on a v5e the
      sparse-C kernel ran 1,227,952 steps of height 8 in 0.402 s,
      0.33 µs each, with 68 KiB of A and B each).

    The padded route keeps the first height. The dense-strip routes keep
    the C row strip ``r·nnb·bn·4`` within the strip budget
    (:func:`compact_grid_ok_ncols`); the ``(r, bn)`` output window of
    ``sparse_c`` does not bind. The ``sharded`` route also keeps
    ``_SHARD_BLOCKS`` row blocks for each of its ``shards`` cores. Ties
    keep the lower height.

    >>> from repro.core.formats import block_r_counts, tiled_layout
    >>> h = HostCSR.from_dense(np.ones((512, 512), np.float32))
    >>> counts = block_r_counts(h, tiled_layout(h, 128, 128)[0],
    ...                         block_k=128, nnb=4)
    >>> [select_block_r(counts, route=r, nrows=512, block_k=128, nnb=4)
    ...  for r in ("padded", "streamed", "sparse_c")]
    [8, 128, 128]
    """
    sparse = route == "sparse_c"
    best_r, best = None, None
    for r, (steps, pairs, windows, *_) in sorted(counts.items()):
        if best_r is not None and (
                route == "padded"
                or not sparse and not compact_grid_ok_ncols(
                    nnb * bn, block_r=r, bn=bn)
                or route == "sharded"
                and (nrows + r - 1) // r < _SHARD_BLOCKS * shards):
            break
        a_slab = r * block_k * 4
        pair = (block_k * bn * b_itemsize + r * block_k * bn // 20
                + _STEP_OVERHEAD_BYTES + (a_slab if sparse else 0))
        c_rows = windows * bn if sparse else (nrows + r - 1) // r * nnb * bn
        score = (steps * a_slab * (1 if sparse else 2) + pairs * pair
                 + c_rows * r * 4)
        if best is None or score < best:
            best_r, best = r, score
    return int(best_r)


def pack_spgemm_pattern(ap: HostCSR, bh: HostCSR, *, block_k: int,
                        a_src: np.ndarray | None = None,
                        b_src: np.ndarray | None = None,
                        b_dtype=jnp.float32,
                        sparse_out: bool = False,
                        mask: HostCSR | None = None,
                        mask_src: np.ndarray | None = None
                        ) -> SpGEMMPattern:
    """Pack ``ap @ bh`` for the Sp×Sp kernels from the patterns alone, and
    choose the route that launches it and A's row-block height.

    ``a_src``/``b_src`` map each nonzero of ``ap``/``bh`` to the nonzero
    of the operand as sent (``HostCSR.permuted``'s ``src``; ``None``: the
    same order), so :meth:`SpGEMMPattern.fill` takes the sent ``data``.
    Nothing is read back from the device: the stream ids, the live pairs
    and the shard partition come from the host layouts
    (:func:`bcc_layout`, :func:`tiled_layout`), with B's tiles ``bn``
    128 wide.

    The route (:class:`SpGEMMPattern`) is chosen here, once, from what
    the patterns and the platform show, at the first height of
    ``_BLOCK_R_HEIGHTS``:

    * B's width against the C row-strip budget
      (:func:`compact_grid_ok_ncols`): the live-pair grid, or the padded
      grid when B is too wide — refused when its stream and B's tile
      table overflow the SMEM budget;
    * B's tile store against ``_RESIDENT_B_BUDGET``: pinned in VMEM, or
      streamed — behind the two-slot prefetch on a TPU;
    * :func:`pallas_shard_count`: sharded over the cores;
    * C's window density, the live windows over all of them, against
      ``_SPARSE_C_DENSITY``, unsharded: the sparse-C grid.

    Then :func:`select_block_r` chooses the row-block height for that
    route from the stream lengths every height would give
    (:func:`repro.core.formats.block_r_counts`), and the pattern is
    packed at it.

    ``sparse_out=True`` packs for :meth:`SpGEMMPattern.run_sparse`: the
    sparse-C route whatever C's predicted density, unsharded. B must then
    be narrow enough for the compacted grid.

    A ``mask`` (a :class:`HostCSR` of the product's shape, in the
    operands' order; ``mask_src`` maps its nonzeros to the mask as sent,
    like ``a_src``) packs the masked product ``(ap @ bh) ∘ mask`` on the
    sparse-C route: the height is chosen on the counts of the masked
    stream, the live pairs keep only the C windows that hold a nonzero
    of the mask before the window-major stream is built, and the
    pattern records where each nonzero of the mask sits in the slabs
    (``mask_pos``). Each pack counts its kept and pruned pairs in
    ``sxs_mask_pairs``.
    """
    bn = _BN
    sparse_out = sparse_out or mask is not None
    table, tile_cap, b_pos = tiled_layout(bh, block_k, bn)
    tiles_shape = (tile_cap, block_k, bn)
    nnb = (bh.ncols + bn - 1) // bn
    counts = block_r_counts(ap, table, block_k=block_k, nnb=nnb,
                            heights=_BLOCK_R_HEIGHTS, mask=mask, bn=bn)
    base = _BLOCK_R_HEIGHTS[0]
    resident = (math.prod(tiles_shape) * jnp.dtype(b_dtype).itemsize
                <= _RESIDENT_B_BUDGET)
    n_shards = 1 if sparse_out else pallas_shard_count()
    if not compact_grid_ok_ncols(nnb * bn, block_r=base, bn=bn):
        if sparse_out:
            raise ValueError(f"B of {bh.ncols} columns is too wide for the "
                             "sparse-C output tier")
        route = "padded"
        kernel = cluster_spgemm_resident if resident else cluster_spgemm_tiled
    elif n_shards > 1:
        route, kernel = "sharded", (cluster_spgemm_pairs_resident if resident
                                    else cluster_spgemm_pairs_db if on_tpu()
                                    else cluster_spgemm_pairs)
    elif sparse_out or counts[base][2] <= _SPARSE_C_DENSITY * nnb * (
            (ap.nrows + base - 1) // base):
        route, kernel = "sparse_c", (cluster_spgemm_pairs_sparse_db
                                     if on_tpu()
                                     else cluster_spgemm_pairs_sparse)
    else:
        route, kernel = (
            ("resident", cluster_spgemm_pairs_resident) if resident
            else ("streamed_db", cluster_spgemm_pairs_db) if on_tpu()
            else ("streamed", cluster_spgemm_pairs))
    block_r = select_block_r(counts, route=route, nrows=ap.nrows,
                             block_k=block_k, nnb=nnb, bn=bn,
                             b_itemsize=jnp.dtype(b_dtype).itemsize,
                             shards=n_shards)
    stream_ids, ntiles, a_pos, values_shape = _compact_layout(
        ap, block_r, block_k)
    a_map = _value_map(a_pos, a_src, math.prod(values_shape))
    b_map = _value_map(b_pos, b_src, math.prod(tiles_shape))
    nblocks = ntiles.shape[0]
    pairs = shards = sparse_pairs = None
    if route == "padded":
        # the padded grid prefetches its whole stream and B's whole table
        prefetch_bytes = 4 * (2 * len(stream_ids[0]) + table.size)
        if prefetch_bytes > _SMEM_STREAM_BUDGET:
            raise ValueError(
                f"padded Sp×Sp grid needs {prefetch_bytes} B of SMEM for "
                f"its stream and B's tile table, over the "
                f"{_SMEM_STREAM_BUDGET} B budget (C row strip of "
                f"{nnb * bn} columns is too wide for the compacted grid)")
    else:
        host_pairs = _live_pairs(
            stream_ids, ntiles, table, nnb=nnb, nblocks=nblocks,
            window_live=None if mask is None else mask_windows(
                mask, block_r=block_r, bn=bn, nblocks=nblocks, nnb=nnb))
        if route == "sharded":
            ranges, shard_pairs = partition_pair_stream(
                host_pairs, nblocks=nblocks, num_shards=n_shards)
            shards = (ranges, [to_device(*p) for p in shard_pairs])
        elif route == "sparse_c":
            *streams, nslabs = _sparse_c_pairs(host_pairs, nblocks=nblocks,
                                               nnb=nnb)
            sparse_pairs = (*to_device(*streams), nslabs)
        pairs = to_device(*host_pairs)
    reg = obs_metrics.get_registry()
    reg.counter("sxs_block_r", route=route, block_r=str(block_r)).inc()
    mask_pos = mask_pairs = None
    if mask is not None:
        kept, unmasked = int(host_pairs[0].size), counts[block_r][3]
        mask_pairs = (kept, unmasked)
        reg.counter("sxs_mask_pairs", pairs="kept").inc(kept)
        reg.counter("sxs_mask_pairs", pairs="pruned").inc(unmasked - kept)
        pos = compacted_c_positions(
            streams[3], expand_indptr(mask.indptr), mask.indices,
            block_r=block_r, bn=bn, nnb=nnb)
        if mask_src is not None:           # into the order as sent
            sent = np.empty_like(pos)
            sent[np.asarray(mask_src)] = pos
            pos = sent
        mask_pos, = to_device(int32_positions(pos))
    table, *ids = to_device(table, *stream_ids)
    return SpGEMMPattern(
        a=BCCShape(ap.nrows, ap.ncols, block_r, block_k),
        b_shape=(bh.nrows, bh.ncols, block_k, bn), table=table,
        stream_ids=tuple(ids), route=route, kernel=kernel, pairs=pairs,
        shards=shards, sparse_pairs=sparse_pairs,
        a_map=to_device(*a_map), b_map=to_device(*b_map),
        values_shape=values_shape, tiles_shape=tiles_shape,
        tiles_dtype=jnp.dtype(b_dtype), mask_pos=mask_pos,
        mask_pairs=mask_pairs)


def int32_positions(pos: np.ndarray) -> np.ndarray:
    """Slab positions as int32, the index type of the device gathers."""
    if pos.size and int(pos.max()) >= 2**31:
        raise ValueError("sparse-C slab store too large for int32 "
                         "positions")
    return pos.astype(np.int32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fused_ssd(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
              c: jax.Array, chunk: int, *,
              interpret: bool | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Drop-in for models.mamba2.ssd_chunked backed by the fused Pallas
    kernel. x (B,S,H,P); dt (B,S,H); a_log (H,); b/c (B,S,G,N) with G
    groups broadcast over heads. Returns (y (B,S,H,P), state (B,H,P,N))."""
    if interpret is None:
        interpret = not on_tpu()
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = s // chunk
    rep = h // g
    a_step = (-jnp.exp(a_log.astype(jnp.float32)))[None, None, :] \
        * dt.astype(jnp.float32)                              # (B,S,H)
    xd = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    def to_bh(t):   # (B,S,H,...) -> (B*H, nc, Q, ...)
        t = jnp.moveaxis(t, 2, 1)                             # (B,H,S,...)
        return t.reshape(bsz * h, nc, chunk, *t.shape[3:])

    bh_b = jnp.broadcast_to(b[:, :, :, None, :], (bsz, s, g, rep, n)
                            ).reshape(bsz, s, h, n)
    bh_c = jnp.broadcast_to(c[:, :, :, None, :], (bsz, s, g, rep, n)
                            ).reshape(bsz, s, h, n)
    y, hfin = ssd_chunk_scan(
        to_bh(xd), to_bh(a_step[..., None])[..., 0],
        to_bh(bh_b.astype(jnp.float32)), to_bh(bh_c.astype(jnp.float32)),
        interpret=interpret)
    y = jnp.moveaxis(y.reshape(bsz, h, s, p), 1, 2).astype(x.dtype)
    state = jnp.moveaxis(hfin.reshape(bsz, h, n, p), 2, 3)    # (B,H,P,N)
    return y, state


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, block_q: int = 128, block_k: int = 128,
              interpret: bool = False) -> jax.Array:
    """GQA flash attention: q (B,Hq,S,D), k/v (B,Hkv,S,D); Hq % Hkv == 0."""
    bsz, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    out = flash_attention(q.reshape(bsz * hq, sq, d),
                          k.reshape(bsz * hq, sk, d),
                          v.reshape(bsz * hq, sk, d),
                          causal=causal, block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return out.reshape(bsz, hq, sq, d)
