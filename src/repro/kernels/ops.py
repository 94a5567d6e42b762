"""Jit'd public wrappers around the Pallas kernels.

These adapt framework-level types (``core.formats.BCC``, GQA-shaped
attention tensors) to the kernel calling conventions, handle padding, and
select interpret mode automatically off-TPU so the same call sites run in
CI (CPU, interpret=True) and production (TPU, compiled).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import (BCC, CompactedC, TiledCSR,
                                compacted_c_counters, compacted_c_from_dense,
                                compacted_c_table, live_pair_counters,
                                live_pair_stream, partition_pair_stream,
                                revisit_pair_stream, revisit_window_blocks)
from repro.core.segment import rank_in_segment
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
           "bcc_spmm_compact", "build_live_pairs", "build_shard_pack",
           "build_sparse_c_pairs", "predict_c_window_density",
           "compact_grid_ok", "compact_grid_ok_ncols", "bcc_spgemm_tiled",
           "bcc_spgemm_sparse_c", "flash_mha", "fused_ssd"]

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

# predicted C window density (live (blk, j) windows / all windows) at or
# below which bcc_spgemm_tiled routes through the sparse-C output tier:
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
    tpb = a.tiles_per_block
    eff = np.maximum(ntiles, 1) if cover_all_blocks else ntiles
    live_mask = np.arange(tpb, dtype=np.int64)[None, :] < eff[:, None]
    keep = np.flatnonzero(live_mask.ravel())
    if keep.size == 0:   # fully empty matrix: single zero step
        keep = np.zeros(1, dtype=np.int64)
    blocks = keep // tpb
    live = keep.shape[0]
    pad = (-live) % 8
    keep = np.concatenate([keep, np.full(pad, keep[-1], dtype=np.int64)])
    block_ids = np.concatenate(
        [blocks, np.full(pad, blocks[-1], dtype=np.int64)]).astype(np.int32)
    vals = values[keep]
    if pad:
        vals[live:] = 0.0
    # slabs of empty blocks (cover_all_blocks) are all-zero by construction
    # in the padded lattice, so their steps contribute nothing
    return block_ids, tile_ids[keep].astype(np.int32), vals


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


def bcc_spmm_compact(a: BCC, b: jax.Array, *, bn: int = 128,
                     interpret: bool | None = None,
                     stream: tuple | None = None) -> jax.Array:
    """C = A_bcc @ B via the compact-stream kernel (no padding compute)."""
    if interpret is None:
        interpret = not on_tpu()
    if stream is None:
        # cover_all_blocks: a block with no live tiles must still appear
        # once so the compact-grid kernel zero-initializes its C strip
        stream = bcc_compact_stream(a, cover_all_blocks=True)
    block_ids, tile_ids, values = to_device(*stream)
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


def compact_grid_ok_ncols(ncols: int, *, block_r: int = 8,
                          bn: int = 128) -> bool:
    """ncols-level form of :func:`compact_grid_ok` at the serving path's
    default packing — the cost model's pre-packing gate for the per-core
    shard discount (one source of truth for the strip-budget rule)."""
    nnb = (max(ncols, 1) + bn - 1) // bn
    return block_r * nnb * bn * 4 <= _COMPACT_C_STRIP_BUDGET


def compact_grid_ok(a: BCC, b: TiledCSR) -> bool:
    """Whether the live-pair compacted grid applies to this operand pair:
    its C output window is a whole ``(block_r, nnb*bn)`` row strip, so B
    matrices wide enough to blow the strip budget fall back to the padded
    per-tile grid. Callers that pre-pack the pair stream (the planner's
    serving path) gate the build on this — the intersection would be
    discarded otherwise."""
    return compact_grid_ok_ncols(b.nnb * b.bn, block_r=a.block_r, bn=b.bn)


def build_live_pairs(a: BCC, b: TiledCSR, stream: tuple | None = None
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Host-side: intersect A's compact stream with B's tile table into
    the live-pair compacted grid (the v2 Sp×Sp kernels' input). Packed
    once per cached operand pair by the planner's serving path.

    Synthetic stream steps — ``cover_all_blocks`` zero slabs of empty
    blocks and the tail padding — are masked out of the pair expansion
    (their slabs are all-zero; the pair grid re-covers their blocks with
    its own zero-slot sentinels).
    """
    if stream is None:
        stream = bcc_compact_stream(a, cover_all_blocks=True)
    block_ids, tile_ids = np.asarray(stream[0]), np.asarray(stream[1])
    ntiles, table = to_host(a.ntiles, b.table)
    step_live = rank_in_segment(block_ids.astype(np.int64)) \
        < ntiles[block_ids]
    return live_pair_stream(
        block_ids, tile_ids, table, nnb=b.nnb,
        nblocks=(a.nrows + a.block_r - 1) // a.block_r,
        step_live=step_live)


def build_shard_pack(a: BCC, b: TiledCSR, pairs: tuple, *,
                     shards: int | None = None,
                     revisit: bool = False) -> tuple | None:
    """Host-side: partition the live-pair stream into per-core contiguous
    block ranges (balanced by live-pair count) and optionally revisit-order
    each core's sub-stream so B tile fetches dedup across blocks. Packed
    once per cached operand pair by the planner's serving path.

    Returns ``(ranges, shard_pairs, window_blocks)`` — the input of
    :func:`repro.kernels.cluster_spgemm.cluster_spgemm_pairs_sharded` —
    or ``None`` when there is nothing to do (one core, no revisit).
    """
    if shards is None:
        shards = pallas_shard_count()
    if shards <= 1 and not revisit:
        return None
    nblocks = (a.nrows + a.block_r - 1) // a.block_r
    ranges, shard_pairs = partition_pair_stream(
        pairs, nblocks=nblocks, num_shards=shards)
    wb = None
    if revisit:
        wb = revisit_window_blocks(b.nnb, block_r=a.block_r, bn=b.bn)
        shard_pairs = [
            revisit_pair_stream(p, window_blocks=wb, block_base=int(s))
            for p, (s, _) in zip(shard_pairs, ranges)]
    return ranges, shard_pairs, wb


def predict_c_window_density(pairs, *, nblocks: int, nnb: int) -> float:
    """Predicted density of C's ``(block_r, bn)`` window lattice: distinct
    live ``(blk, j)`` windows over all ``nblocks × nnb`` windows — known
    *before* the numeric phase from the live-pair stream alone (a window
    with no live pair is provably zero). This is the output-density
    threshold :func:`bcc_spgemm_tiled` auto-selects dense-strip vs
    sparse-C on: the sparse tier's C bytes are exactly ``density`` of the
    dense strips'."""
    blocks, js, slots, _ = (np.asarray(p) for p in pairs)
    live = slots > 0
    key = blocks[live].astype(np.int64) * nnb + js[live].astype(np.int64)
    return np.unique(key).size / max(nblocks * nnb, 1)


def build_sparse_c_pairs(a: BCC, b: TiledCSR, pairs: tuple | None = None,
                         stream: tuple | None = None, *, pad_to: int = 8
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray, int]:
    """Host-side: re-sort the live-pair stream window-major for the
    sparse-C kernels and tag each pair with its destination
    :class:`repro.core.formats.CompactedC` slab.

    The dense kernels need (block, s, j) order — one C *strip* per block,
    visited once. The sparse-C kernels' output block is one ``(blk, j)``
    *window*, so the stream re-sorts by (blk, j, s): every slab is
    visited contiguously (Pallas writes an output block back when its
    index changes; revisiting would clobber), and within a window pairs
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
    if stream is None:
        stream = bcc_compact_stream(a, cover_all_blocks=True)
    if pairs is None:
        pairs = build_live_pairs(a, b, stream)
    nblocks = (a.nrows + a.block_r - 1) // a.block_r
    table, nlive = compacted_c_table(pairs, nblocks=nblocks, nnb=b.nnb)
    blocks, js, slots, a_idx = (np.asarray(p) for p in pairs)
    live = slots > 0
    bl = blocks[live].astype(np.int64)
    jl = js[live].astype(np.int64)
    sl = slots[live]
    al = a_idx[live]
    order = np.lexsort((al, jl, bl))
    bl, jl, sl, al = bl[order], jl[order], sl[order], al[order]
    c_slots = table[bl * b.nnb + jl].astype(np.int64)
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


def bcc_spgemm_sparse_c(a: BCC, b: TiledCSR, *,
                        interpret: bool | None = None,
                        stream: tuple | None = None,
                        pairs: tuple | None = None,
                        sparse_pairs: tuple | None = None,
                        double_buffer: bool | None = None,
                        epilogue: str | None = None) -> CompactedC:
    """C = A_bcc @ B_tiled into the sparse-C output tier: the numeric
    phase accumulates each live C window in VMEM exactly like the
    dense-strip kernels but writes back *only* the live windows as
    packed :class:`repro.core.formats.CompactedC` slabs — C bytes to HBM
    scale with nnz(C)'s window footprint, not ``rows × nnb·bn``.

    ``epilogue`` selects where the compaction happens:
      * ``"kernel"`` — the windowed-scatter epilogue runs inside the
        Pallas kernel (its output BlockSpec scatters straight into the
        slab store). Default on TPU; also interpret-capable, which is
        what the bit-identity tests exercise.
      * ``"xla"`` — dense-strip product first, then an XLA
        segment-compaction gather of the live windows
        (:func:`repro.core.formats.compacted_c_from_dense`). Default
        off-TPU; same table, bit-identical slabs.

    ``sparse_pairs`` overrides the packed window-major stream
    (:func:`build_sparse_c_pairs` — cached per operand pair by the
    planner's chain workload).
    """
    _faults.maybe_fault("kernel_launch")
    if interpret is None:
        interpret = not on_tpu()
    if a.block_k != b.block_k:
        raise ValueError(f"A block_k {a.block_k} != B block_k {b.block_k}")
    if stream is None:
        stream = bcc_compact_stream(a, cover_all_blocks=True)
    if sparse_pairs is None:
        sparse_pairs = build_sparse_c_pairs(a, b, pairs, stream)
    c_slots, slots, a_idx, table, nslabs = sparse_pairs
    if epilogue is None:
        epilogue = "kernel" if on_tpu() else "xla"
    if epilogue == "xla":
        dense = bcc_spgemm_tiled(a, b, interpret=interpret, stream=stream,
                                 pairs=pairs, sparse_c=False)
        return compacted_c_from_dense(dense, table, nrows=a.nrows,
                                      ncols=b.ncols, block_r=a.block_r,
                                      bn=b.bn)
    if epilogue != "kernel":
        raise ValueError(f"unknown epilogue '{epilogue}'")
    values, c_slots, slots, a_idx, table = to_device(
        stream[2], c_slots, slots, a_idx, table)
    db = double_buffer if double_buffer is not None else on_tpu()
    kernel = (cluster_spgemm_pairs_sparse_db if db
              else cluster_spgemm_pairs_sparse)
    with get_tracer().span("kernel_variant", variant="sparse_c",
                           epilogue="kernel"):
        slabs = kernel(c_slots, slots, a_idx, values, b.tiles,
                       block_r=a.block_r, block_k=a.block_k, bn=b.bn,
                       nslabs=int(nslabs), chunk=stream_chunk(3),
                       interpret=interpret)
    out = CompactedC(slabs=slabs, table=table,
                     nrows=a.nrows, ncols=b.ncols,
                     block_r=a.block_r, bn=b.bn)
    _note_kernel_launch("sparse_c", cc=out)
    return out


def bcc_spgemm_tiled(a: BCC, b: TiledCSR, *,
                     interpret: bool | None = None,
                     stream: tuple | None = None,
                     pairs: tuple | None = None,
                     resident: bool | None = None,
                     compact: bool | None = None,
                     double_buffer: bool | None = None,
                     shards: int | None = None,
                     revisit: bool = False,
                     shard_pack: tuple | None = None,
                     sparse_c: bool | None = None) -> jax.Array:
    """C = A_bcc @ B_tiled via the Pallas Sp×Sp kernel tier. Returns the
    dense ``(a.nrows, b.ncols)`` product (fp32 — bf16 B tiles are upcast
    at the MXU input, accumulation stays fp32).

    Variant selection:
      * ``compact`` — run the live-pair compacted grid (v2, default) vs
        the PR-3 padded ``(nnb, S)`` grid. Auto-falls back to the padded
        grid when the C row-strip window would exceed its VMEM budget.
      * ``resident`` pins B's tile store in VMEM (one HBM fetch for all
        of B); default: auto — resident when the store fits
        ``_RESIDENT_B_BUDGET``.
      * ``double_buffer`` — for the compact *streamed* path, prefetch the
        next B tile into a two-slot scratch while the current one
        contracts. Default: on for compiled TPU runs, off in interpret
        mode (correct there too, just slower to simulate).
      * ``stream`` / ``pairs`` override the packed A compact stream and
        the live-pair grid (packed once per operand by callers that
        reuse the plan).
      * ``shards`` — fan the compacted grid out over this many cores
        (contiguous block ranges balanced by live-pair count, disjoint C
        row strips, no cross-core accumulation). Default: auto —
        ``pallas_shard_count()``, i.e. every TPU core and 1 off-TPU
        (where the identical partition runs serially).
      * ``revisit`` — B-fetch-deduping revisit order: each core's
        sub-stream is resorted (j, slot, block) within VMEM-budget
        windows so the streamed-B DMA elision fetches each live tile
        once per window instead of once per touching block. Bit-identical
        output; counter-visible in ``live_pair_counters`` /
        ``bench_kernels``. Off by default (the resident variants already
        fetch B once; the win is for streamed, HBM-resident B).
      * ``shard_pack`` overrides the packed partition
        (:func:`build_shard_pack`, cached by the planner's serving path).
      * ``sparse_c`` — route the unsharded compact path through the
        sparse-C output tier (:func:`bcc_spgemm_sparse_c`) and densify
        the :class:`repro.core.formats.CompactedC` result on the way out
        (bit-identical values; C HBM writes scale with the live-window
        count). Default: auto — sparse when the predicted C window
        density (:func:`predict_c_window_density`) is at most
        ``_SPARSE_C_DENSITY`` and the product is not sharded; callers
        that want the compacted format itself call
        :func:`bcc_spgemm_sparse_c` directly.
    """
    _faults.maybe_fault("kernel_launch")
    if interpret is None:
        interpret = not on_tpu()
    if a.block_k != b.block_k:
        raise ValueError(f"A block_k {a.block_k} != B block_k {b.block_k}")
    nkb_needed = (a.ncols + a.block_k - 1) // a.block_k
    if b.nkb < nkb_needed:
        raise ValueError(f"B covers {b.nkb} k-blocks, A addresses "
                         f"{nkb_needed}")
    if stream is None:
        stream = bcc_compact_stream(a, cover_all_blocks=True)
    if compact is None:
        # an explicitly pre-packed pair stream means the caller already
        # decided (and paid) for the compacted grid — honor it
        compact = True if pairs is not None else compact_grid_ok(a, b)
    if resident is None:
        resident = b.nbytes_tiles() <= _RESIDENT_B_BUDGET
    nblocks = (a.nrows + a.block_r - 1) // a.block_r
    if compact:
        if pairs is None:
            pairs = build_live_pairs(a, b, stream)
        if shard_pack is None:
            shard_pack = build_shard_pack(a, b, pairs, shards=shards,
                                          revisit=revisit)
        if sparse_c is None:
            sparse_c = (shard_pack is None
                        and predict_c_window_density(
                            pairs, nblocks=nblocks, nnb=b.nnb)
                        <= _SPARSE_C_DENSITY)
        if sparse_c and shard_pack is None:
            cc = bcc_spgemm_sparse_c(
                a, b, interpret=interpret, stream=stream, pairs=pairs,
                double_buffer=double_buffer, epilogue="kernel")
            return cc.to_dense()
        if shard_pack is not None:
            ranges, shard_pairs, wb = shard_pack
            variant = "sharded_revisit" if wb is not None else "sharded"
            values, = to_device(stream[2])
            with get_tracer().span("kernel_variant", variant=variant,
                                   shards=len(shard_pairs)):
                out = cluster_spgemm_pairs_sharded(
                    shard_pairs, ranges, values, b.tiles,
                    block_r=a.block_r, block_k=a.block_k, bn=b.bn,
                    nblocks=nblocks, nnb=b.nnb, window_blocks=wb,
                    resident=bool(resident) and wb is None,
                    double_buffer=(double_buffer
                                   if double_buffer is not None
                                   else on_tpu()),
                    chunk=stream_chunk(4 if wb is None else 5),
                    interpret=interpret)
            _note_kernel_launch(variant, pairs=pairs, block_r=a.block_r,
                                block_k=a.block_k, bn=b.bn)
            return out[: a.nrows, : b.ncols]
        values, blocks, js, slots, a_idx = to_device(stream[2], *pairs)
        if resident:
            kernel, variant = cluster_spgemm_pairs_resident, "resident"
        elif double_buffer if double_buffer is not None else on_tpu():
            kernel, variant = cluster_spgemm_pairs_db, "streamed_db"
        else:
            kernel, variant = cluster_spgemm_pairs, "streamed"
        with get_tracer().span("kernel_variant", variant=variant):
            out = kernel(blocks, js, slots, a_idx, values, b.tiles,
                         block_r=a.block_r, block_k=a.block_k, bn=b.bn,
                         nblocks=nblocks, nnb=b.nnb, chunk=stream_chunk(4),
                         interpret=interpret)
        _note_kernel_launch(variant, pairs=pairs, block_r=a.block_r,
                            block_k=a.block_k, bn=b.bn)
        return out[: a.nrows, : b.ncols]
    # the padded grid prefetches its whole stream and B's whole table
    prefetch_bytes = 4 * (2 * len(stream[0]) + b.nkb * b.nnb)
    if prefetch_bytes > _SMEM_STREAM_BUDGET:
        raise ValueError(
            f"padded Sp×Sp grid needs {prefetch_bytes} B of SMEM for its "
            f"stream and B's tile table, over the {_SMEM_STREAM_BUDGET} B "
            f"budget (C row strip of {b.nnb * b.bn} columns is too wide "
            "for the compacted grid)")
    block_ids, tile_ids, values = to_device(*stream)
    kernel = cluster_spgemm_resident if resident else cluster_spgemm_tiled
    with get_tracer().span("kernel_variant", variant="padded",
                           resident=bool(resident)):
        out = kernel(block_ids, tile_ids, b.table, values, b.tiles,
                     block_r=a.block_r, block_k=a.block_k, bn=b.bn,
                     nblocks=nblocks, nnb=b.nnb, interpret=interpret)
    _note_kernel_launch("padded")
    return out[: a.nrows, : b.ncols]


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
