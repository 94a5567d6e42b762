"""Sparse matrix formats: CSR, CSR_Cluster, and BCC (block-clustered-columns).

Two tiers:

* **Host tier** (`HostCSR`) — plain numpy, ragged, used by the preprocessing
  pipeline (reordering, clustering, format construction). Mirrors the paper's
  CPU-side CSR exactly.
* **Device tier** (`CSR`, `CSRCluster`, `BCC`) — JAX pytrees with *static*
  shapes (padded capacities) so every kernel jits. Padding convention:
  ``col == ncols`` sentinel / zero values contribute nothing.

The CSR_Cluster device layout pads rows-in-cluster to ``max_cluster`` (K) so
the value slab is a rectangular ``(col_slots, K)`` array — column ids are
still deduplicated per cluster, which is the format's memory win. The *exact*
ragged footprint the paper reports (Fig. 11) is computed analytically by
:func:`csr_cluster_nbytes_exact` without materializing the ragged layout.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.segment import (boundary_mask, expand_indptr, key_table,
                                ragged_gather_indices, segmented_count,
                                segmented_sum)

__all__ = [
    "HostCSR",
    "Masked",
    "BlockDiagPack",
    "block_diag_csr",
    "block_diag_csr_reference",
    "split_block_diag",
    "CSR",
    "CSRCluster",
    "BCC",
    "BCCShape",
    "TiledCSR",
    "CompactedC",
    "csr_from_host",
    "csr_cluster_from_host",
    "csr_cluster_from_host_reference",
    "bcc_from_host",
    "bcc_from_host_reference",
    "bcc_layout",
    "tiled_csr_from_host",
    "tiled_csr_from_host_reference",
    "tiled_layout",
    "scatter_map",
    "tiled_live_tiles",
    "select_block_k",
    "live_pair_stream",
    "live_pair_stream_reference",
    "live_pair_counters",
    "partition_pair_stream",
    "partition_pair_stream_reference",
    "partition_balance",
    "tile_col_occupancy",
    "symbolic_strip_nnz",
    "symbolic_strip_nnz_reference",
    "compacted_c_table",
    "compacted_c_positions",
    "mask_windows",
    "compacted_c_from_dense",
    "compacted_c_to_host",
    "compacted_c_counters",
    "COUNTER_UNITS",
    "csr_cluster_nbytes_exact",
    "csr_cluster_nbytes_exact_reference",
    "csr_nbytes",
]

# ---------------------------------------------------------------------------
# Host tier
# ---------------------------------------------------------------------------


class HostCSR:
    """Numpy CSR with the preprocessing operations the paper needs.

    Invariants: ``indptr`` is int64 non-decreasing of length ``nrows+1``;
    column indices within a row are sorted ascending; no explicit zeros
    required (but tolerated).
    """

    # __weakref__ so the serving boundary's validation memo (a
    # WeakValueDictionary on ResiliencePolicy) can hold operands without
    # pinning them
    __slots__ = ("indptr", "indices", "data", "shape", "__weakref__")

    def __init__(self, indptr, indices, data, shape):
        self.indptr = np.asarray(indptr, dtype=np.int64)
        self.indices = np.asarray(indices, dtype=np.int32)
        self.data = np.asarray(data, dtype=np.float32)
        self.shape = (int(shape[0]), int(shape[1]))
        if self.indptr.shape[0] != self.shape[0] + 1:
            raise ValueError("indptr length mismatch")
        if self.indices.shape[0] != self.data.shape[0]:
            raise ValueError("indices/data length mismatch")

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_coo(cls, rows, cols, vals, shape, *, sum_duplicates=True) -> "HostCSR":
        """Build from COO triplets (duplicates summed by default).

        >>> h = HostCSR.from_coo([0, 1], [1, 0], [3.0, 4.0], (2, 2))
        >>> h.to_dense()
        array([[0., 3.],
               [4., 0.]], dtype=float32)
        >>> h.nnz, h.row_nnz().tolist()
        (2, [1, 1])
        """
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        vals = np.asarray(vals, dtype=np.float32)
        nrows, ncols = shape
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        if sum_duplicates and rows.size:
            key = rows * ncols + cols
            uniq, inv = np.unique(key, return_inverse=True)
            newv = np.zeros(uniq.shape[0], dtype=np.float64)
            np.add.at(newv, inv, vals)
            rows = (uniq // ncols).astype(np.int64)
            cols = (uniq % ncols).astype(np.int64)
            vals = newv.astype(np.float32)
        indptr = np.zeros(nrows + 1, dtype=np.int64)
        np.add.at(indptr, rows + 1, 1)
        np.cumsum(indptr, out=indptr)
        return cls(indptr, cols.astype(np.int32), vals, shape)

    @classmethod
    def from_dense(cls, dense) -> "HostCSR":
        dense = np.asarray(dense)
        rows, cols = np.nonzero(dense)
        return cls.from_coo(rows, cols, dense[rows, cols], dense.shape,
                            sum_duplicates=False)

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.float32)
        out[expand_indptr(self.indptr), self.indices] = self.data
        return out

    # -- basic properties ----------------------------------------------------

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def ncols(self) -> int:
        return self.shape[1]

    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr)

    def row(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        s, e = self.indptr[i], self.indptr[i + 1]
        return self.indices[s:e], self.data[s:e]

    def validate(self, name: str = "operand") -> "HostCSR":
        """Check every structural invariant (monotone ``indptr``, in-range
        sorted ``indices``, finite ``data``, consistent lengths); raises
        :class:`repro.resilience.errors.InvalidOperandError` naming the
        violated invariant. Returns ``self`` for chaining."""
        # lazy import: resilience sits above core in the layer order
        from repro.resilience.validation import validate_host_csr
        validate_host_csr(self, name=name)
        return self

    # -- transforms ----------------------------------------------------------

    def binarize(self) -> "HostCSR":
        return HostCSR(self.indptr, self.indices,
                       np.ones_like(self.data), self.shape)

    def transpose(self) -> "HostCSR":
        """O(nnz) counting transpose (Gustavson's permuted transposition)."""
        nrows, ncols = self.shape
        cnt = np.zeros(ncols + 1, dtype=np.int64)
        np.add.at(cnt, self.indices.astype(np.int64) + 1, 1)
        indptr_t = np.cumsum(cnt)
        indices_t = np.empty(self.nnz, dtype=np.int32)
        data_t = np.empty(self.nnz, dtype=np.float32)
        # expand row ids then stable-sort by column
        row_ids = np.repeat(np.arange(nrows, dtype=np.int32), self.row_nnz())
        order = np.argsort(self.indices, kind="stable")
        indices_t[:] = row_ids[order]
        data_t[:] = self.data[order]
        return HostCSR(indptr_t, indices_t, data_t, (ncols, nrows))

    def permute_rows(self, perm: np.ndarray) -> "HostCSR":
        """Return A[perm, :] — ``perm[new_row] = old_row``."""
        return self.permuted(perm, symmetric=False)[0]

    def permute_symmetric(self, perm: np.ndarray) -> "HostCSR":
        """Return PAPᵀ — rows and columns permuted together (square only)."""
        return self.permuted(perm, symmetric=True)[0]

    def permuted(self, perm: np.ndarray, *, symmetric: bool
                 ) -> tuple["HostCSR", np.ndarray]:
        """``(PAPᵀ if symmetric else A[perm, :], src)``: ``src[i]`` is the
        nonzero of ``self`` that the permuted matrix's nonzero ``i`` came
        from, so its ``data`` is ``self.data[src]``."""
        if symmetric and self.nrows != self.ncols:
            raise ValueError("symmetric permutation needs a square matrix")
        perm = np.asarray(perm, dtype=np.int64)
        counts = self.row_nnz()[perm]
        indptr = np.zeros(self.nrows + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        src = ragged_gather_indices(self.indptr[perm], counts)
        indices = self.indices[src]
        if symmetric:
            inv = np.empty_like(perm)
            inv[perm] = np.arange(perm.shape[0])
            # remap then segmented-sort column ids within each row: one
            # lexsort keyed (row, newcol) re-sorts every row at once
            newcols = inv[indices.astype(np.int64)].astype(np.int32)
            order = np.lexsort((newcols, expand_indptr(indptr)))
            src, indices = src[order], newcols[order]
        return HostCSR(indptr, indices, self.data[src], self.shape), src

    def jaccard(self, i: int, j: int) -> float:
        """Jaccard similarity of the column-id sets of rows i and j."""
        a, _ = self.row(i)
        b, _ = self.row(j)
        if a.size == 0 and b.size == 0:
            return 1.0
        inter = np.intersect1d(a, b, assume_unique=True).size
        union = a.size + b.size - inter
        return inter / union if union else 0.0

    def nbytes(self, index_bytes: int = 4, value_bytes: int = 4,
               ptr_bytes: int = 8) -> int:
        return (self.indptr.size * ptr_bytes
                + self.indices.size * index_bytes
                + self.data.size * value_bytes)


@dataclasses.dataclass(frozen=True)
class Masked:
    """The right operand of a masked product request: ``submit(a,
    Masked(b, mask))`` asks for ``(a · b) ∘ mask``, the values of the
    product at the nonzeros of ``mask`` times ``mask``'s values, and
    nothing else of it. ``b=None`` is the square ``a · a``. ``mask`` is a
    :class:`HostCSR` of the product's shape; the answer is a
    :class:`HostCSR` on exactly its pattern, with explicit zeros where
    no product term lands."""

    b: "HostCSR | None"
    mask: HostCSR


# ---------------------------------------------------------------------------
# Block-diagonal batching (cross-request packing)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockDiagPack:
    """One block-diagonal packing of N member matrices.

    ``host`` is the packed :class:`HostCSR` of shape
    ``(Σ nrows_i, Σ ncols_i)`` whose i-th diagonal block is member i;
    ``row_offsets`` / ``col_offsets`` are the ``(N+1,)`` prefix sums that
    locate each member's row strip and column band. Because the members
    share no rows *and* no columns, any product of two conforming packs
    is itself block-diagonal: member i's product is exactly the
    ``[row_offsets[i]:row_offsets[i+1], col_offsets[i]:col_offsets[i+1]]``
    block of the packed product (cross blocks are structurally zero), so
    the per-request split is a pure slice — no arithmetic, hence
    bit-identical to computing the member alone with the same kernel.
    """

    host: HostCSR
    row_offsets: np.ndarray            # (N+1,) int64
    col_offsets: np.ndarray            # (N+1,) int64

    @property
    def members(self) -> int:
        return int(self.row_offsets.shape[0] - 1)


def block_diag_csr(mats: Sequence[HostCSR]) -> BlockDiagPack:
    """Pack ``mats`` into one block-diagonal :class:`HostCSR`.

    Vectorized: one concatenation per CSR array — the member indptr
    diffs concatenate directly (prefix-summed once), member column
    indices shift by the column offset of their band, values concatenate
    untouched (so the packed operand is bit-for-bit the members' data).

    >>> a = HostCSR.from_dense([[1.0, 2.0], [0.0, 3.0]])
    >>> b = HostCSR.from_dense([[4.0]])
    >>> block_diag_csr([a, b]).host.to_dense()
    array([[1., 2., 0.],
           [0., 3., 0.],
           [0., 0., 4.]], dtype=float32)
    """
    if not mats:
        raise ValueError("block_diag_csr needs at least one member")
    row_off = np.zeros(len(mats) + 1, dtype=np.int64)
    col_off = np.zeros(len(mats) + 1, dtype=np.int64)
    row_off[1:] = np.cumsum([m.nrows for m in mats])
    col_off[1:] = np.cumsum([m.ncols for m in mats])
    indptr = np.zeros(row_off[-1] + 1, dtype=np.int64)
    if mats:
        np.concatenate([np.diff(m.indptr) for m in mats],
                       out=indptr[1:])
        np.cumsum(indptr, out=indptr)
    indices = np.concatenate(
        [m.indices.astype(np.int64) + col_off[i]
         for i, m in enumerate(mats)]) if mats else np.zeros(0, np.int64)
    data = np.concatenate([m.data for m in mats])
    host = HostCSR(indptr, indices.astype(np.int32), data,
                   (int(row_off[-1]), int(col_off[-1])))
    return BlockDiagPack(host=host, row_offsets=row_off,
                         col_offsets=col_off)


def block_diag_csr_reference(mats: Sequence[HostCSR]) -> BlockDiagPack:
    """Loop oracle for :func:`block_diag_csr`: row-by-row COO append."""
    if not mats:
        raise ValueError("block_diag_csr_reference needs >= 1 member")
    rows, cols, vals = [], [], []
    r0 = c0 = 0
    offsets_r, offsets_c = [0], [0]
    for m in mats:
        for i in range(m.nrows):
            idx, dat = m.row(i)
            for j, v in zip(idx, dat):
                rows.append(r0 + i)
                cols.append(c0 + int(j))
                vals.append(float(v))
        r0 += m.nrows
        c0 += m.ncols
        offsets_r.append(r0)
        offsets_c.append(c0)
    host = HostCSR.from_coo(rows, cols, vals, (r0, c0),
                            sum_duplicates=False)
    return BlockDiagPack(host=host,
                         row_offsets=np.asarray(offsets_r, np.int64),
                         col_offsets=np.asarray(offsets_c, np.int64))


def split_block_diag(dense_c, row_pack: BlockDiagPack,
                     col_pack: BlockDiagPack | None = None
                     ) -> list[np.ndarray]:
    """Slice a packed product back into per-member dense blocks.

    ``row_pack`` locates the row strips (the packed A); ``col_pack``
    locates the column bands — the packed B for an A·B batch, defaulting
    to ``row_pack`` for the A² batch where C's columns are A's. Each
    returned block is a contiguous copy, so member results stay alive
    independently of the batched buffer.
    """
    col_pack = col_pack if col_pack is not None else row_pack
    if row_pack.members != col_pack.members:
        raise ValueError("row/col packs disagree on member count")
    dense_c = np.asarray(dense_c)
    ro, co = row_pack.row_offsets, col_pack.col_offsets
    return [np.ascontiguousarray(dense_c[ro[i]:ro[i + 1],
                                         co[i]:co[i + 1]])
            for i in range(row_pack.members)]


# ---------------------------------------------------------------------------
# Device tier
# ---------------------------------------------------------------------------


def _register(cls):
    fields = [f.name for f in dataclasses.fields(cls)]
    data = [f for f in fields if f not in cls._static]
    jax.tree_util.register_dataclass(cls, data_fields=data,
                                     meta_fields=list(cls._static))
    return cls


@_register
@dataclasses.dataclass(frozen=True)
class CSR:
    """Static-shape CSR: padded to ``nnz_cap``; pad cols == ncols, vals 0."""

    _static = ("nrows", "ncols")

    indptr: jax.Array        # (nrows+1,) int32
    indices: jax.Array       # (nnz_cap,) int32, padded with ncols
    data: jax.Array          # (nnz_cap,) float
    nrows: int
    ncols: int

    @property
    def nnz_cap(self) -> int:
        return self.indices.shape[0]

    def to_dense(self) -> jax.Array:
        row_ids = jnp.searchsorted(
            self.indptr, jnp.arange(self.nnz_cap, dtype=jnp.int32),
            side="right") - 1
        valid = self.indices < self.ncols
        rows = jnp.where(valid, row_ids, 0)
        cols = jnp.where(valid, self.indices, 0)
        vals = jnp.where(valid, self.data, 0.0)
        out = jnp.zeros((self.nrows, self.ncols), self.data.dtype)
        return out.at[rows, cols].add(vals)


@_register
@dataclasses.dataclass(frozen=True)
class CSRCluster:
    """Device CSR_Cluster (paper Fig. 6), rows-in-cluster padded to K.

    ``col_slots`` indexes the deduplicated (cluster, column) pairs:
      * ``cluster_ptr[c] .. cluster_ptr[c+1]`` — slots of cluster ``c``
      * ``cols[s]`` — column id of slot ``s`` (pad: ncols)
      * ``values[s, k]`` — value of row ``row_base[c]+k`` at that column
        (pad: 0 where the row has no entry there or k >= cluster_size[c])
    ``row_base``/``cluster_size`` recover original row ids (clusters cover
    consecutive rows of the — possibly reordered — matrix).
    """

    _static = ("nrows", "ncols", "max_cluster")

    cluster_ptr: jax.Array   # (nclusters+1,) int32
    cols: jax.Array          # (slot_cap,) int32, pad=ncols
    values: jax.Array        # (slot_cap, K) float
    row_base: jax.Array      # (nclusters,) int32
    cluster_size: jax.Array  # (nclusters,) int32
    nrows: int
    ncols: int
    max_cluster: int

    @property
    def nclusters(self) -> int:
        return self.row_base.shape[0]

    @property
    def slot_cap(self) -> int:
        return self.cols.shape[0]

    def to_dense(self) -> jax.Array:
        slot_cluster = jnp.searchsorted(
            self.cluster_ptr, jnp.arange(self.slot_cap, dtype=jnp.int32),
            side="right") - 1
        base = self.row_base[jnp.clip(slot_cluster, 0, self.nclusters - 1)]
        valid_col = self.cols < self.ncols
        out = jnp.zeros((self.nrows + self.max_cluster, self.ncols + 1),
                        self.values.dtype)
        k = jnp.arange(self.max_cluster, dtype=jnp.int32)
        rows = base[:, None] + k[None, :]                       # (S, K)
        cols = jnp.where(valid_col, self.cols, self.ncols)[:, None]
        cols = jnp.broadcast_to(cols, rows.shape)
        out = out.at[rows, cols].add(self.values)
        return out[: self.nrows, : self.ncols]


@_register
@dataclasses.dataclass(frozen=True)
class BCC:
    """Block-Clustered-Columns: the TPU-native clustered format.

    Clusters are fixed-height row blocks of ``block_r`` rows; active columns
    are grouped into ``block_k``-wide tiles. Per cluster we store the list of
    active tile ids (padded with 0 alongside all-zero value slabs) and dense
    ``(block_r, block_k)`` value slabs — MXU-ready.

    ``tile_ids``/``values`` are *flat* over (cluster, tile-slot) with a fixed
    ``tiles_per_block`` stride so a Pallas kernel can scalar-prefetch
    ``tile_ids`` and drive its B BlockSpec index_map with it.
    """

    _static = ("nrows", "ncols", "block_r", "block_k", "tiles_per_block")

    tile_ids: jax.Array      # (nblocks * tiles_per_block,) int32, pad=0
    values: jax.Array        # (nblocks * tiles_per_block, block_r, block_k)
    ntiles: jax.Array        # (nblocks,) int32 — live tiles per block
    nrows: int
    ncols: int
    block_r: int
    block_k: int
    tiles_per_block: int

    @property
    def nblocks(self) -> int:
        return self.ntiles.shape[0]

    def to_dense(self) -> jax.Array:
        nb, t = self.nblocks, self.tiles_per_block
        out = jnp.zeros((nb * self.block_r,
                         (self.ncols + self.block_k - 1)
                         // self.block_k * self.block_k),
                        self.values.dtype)
        for b in range(nb):
            for s in range(t):
                flat = b * t + s
                live = s < self.ntiles[b]
                col0 = self.tile_ids[flat] * self.block_k
                slab = jnp.where(live, self.values[flat], 0.0)
                out = jax.lax.dynamic_update_slice(
                    out,
                    jax.lax.dynamic_slice(
                        out, (b * self.block_r, col0),
                        (self.block_r, self.block_k)) + slab,
                    (b * self.block_r, col0))
        return out[: self.nrows, : self.ncols]


class BCCShape(NamedTuple):
    """What the Sp×Sp launchers read of a :class:`BCC` once its compact
    stream is packed: its shape and blocking, without its value lattice."""

    nrows: int
    ncols: int
    block_r: int
    block_k: int


@_register
@dataclasses.dataclass(frozen=True)
class TiledCSR:
    """Tiled-sparse B operand for the Pallas Sp×Sp kernel.

    B is cut into a ``(nkb × nnb)`` lattice of ``(block_k, bn)`` tiles;
    only *live* tiles (those holding at least one nonzero) are stored, as
    dense MXU-ready slabs. Layout::

        tiles : (tile_cap, block_k, bn)   tiles[0] is the reserved all-zero
                                          tile; live tiles occupy 1..ntiles
        table : (nkb * nnb,) int32        (k-block kb, n-tile nb) → tile
                                          slot at table[kb * nnb + nb];
                                          0 = dead (points at the zero tile)

    The flat ``table`` is what a Pallas kernel scalar-prefetches: together
    with a BCC A's ``tile_ids`` stream it forms the double indirection
    "A's live (block, k-tile) → B's resident tile" of
    :func:`repro.kernels.cluster_spgemm.cluster_spgemm_tiled`. Dense tiles
    carry no column indices — the 8 B/nonzero (index+value) of the CSR
    gather path becomes 4 B/slot of pure values.
    """

    _static = ("nrows", "ncols", "block_k", "bn")

    tiles: jax.Array         # (tile_cap, block_k, bn)
    table: jax.Array         # (nkb * nnb,) int32, 0 = dead
    nrows: int
    ncols: int
    block_k: int
    bn: int

    @property
    def nkb(self) -> int:
        return (self.nrows + self.block_k - 1) // self.block_k

    @property
    def nnb(self) -> int:
        return (self.ncols + self.bn - 1) // self.bn

    @property
    def tile_cap(self) -> int:
        return self.tiles.shape[0]

    @property
    def ntiles_live(self) -> int:
        """Live tiles (excludes the reserved zero tile)."""
        return int((np.asarray(self.table) > 0).sum())

    def nbytes_tiles(self) -> int:
        """HBM footprint of the tile store — what one full streaming of B
        into VMEM costs the kernel."""
        return int(self.tiles.size * self.tiles.dtype.itemsize)

    def to_dense(self) -> jax.Array:
        nkb, nnb = self.nkb, self.nnb
        table = self.table.reshape(nkb, nnb)
        out = jnp.zeros((nkb * self.block_k, nnb * self.bn),
                        self.tiles.dtype)
        for kb in range(nkb):
            for nb in range(nnb):
                out = jax.lax.dynamic_update_slice(
                    out, self.tiles[table[kb, nb]],
                    (kb * self.block_k, nb * self.bn))
        return out[: self.nrows, : self.ncols]


@_register
@dataclasses.dataclass(frozen=True)
class CompactedC:
    """Sparse-C output format of the two-phase Sp×Sp pipeline.

    The dense kernels write every ``(block_r, bn)`` window of C back to
    HBM, live or dead. ``CompactedC`` keeps only the *live* windows —
    those the symbolic pass (:func:`symbolic_strip_nnz` /
    :func:`compacted_c_table`) proves can hold a nonzero — as packed
    value slabs, mirroring :class:`TiledCSR`'s layout on the output
    side::

        slabs : (slab_cap, block_r, bn)   slabs[0] is the reserved
                                          all-zero slab; live windows
                                          occupy 1..nslabs_live
        table : (nblocks * nnb,) int32    (row block blk, col strip j) →
                                          slab at table[blk * nnb + j];
                                          0 = dead (the zero slab)

    Slot **0 is reserved** (the ``TiledCSR`` zero-slot sentinel carried
    to the output): dead windows cost no HBM write and no storage, yet
    read back exactly zero through the table — so C bytes written scale
    with nnz(C)'s window footprint, not ``rows × nnb·bn``.
    """

    _static = ("nrows", "ncols", "block_r", "bn")

    slabs: jax.Array         # (slab_cap, block_r, bn)
    table: jax.Array         # (nblocks * nnb,) int32, 0 = dead
    nrows: int
    ncols: int
    block_r: int
    bn: int

    @property
    def nblocks(self) -> int:
        return (self.nrows + self.block_r - 1) // self.block_r

    @property
    def nnb(self) -> int:
        return (self.ncols + self.bn - 1) // self.bn

    @property
    def slab_cap(self) -> int:
        return self.slabs.shape[0]

    @property
    def nslabs_live(self) -> int:
        """Live windows (excludes the reserved zero slab)."""
        return int((np.asarray(self.table) > 0).sum())

    def nbytes_slabs(self) -> int:
        """HBM footprint of the slab store — what the numeric kernel
        writes back instead of the dense row strips."""
        return int(self.slabs.size * self.slabs.dtype.itemsize)

    def to_dense(self) -> jax.Array:
        # one gather through the table, window-major → row-major reshape
        windows = self.slabs[self.table]         # (nblocks*nnb, br, bn)
        out = windows.reshape(self.nblocks, self.nnb, self.block_r,
                              self.bn).transpose(0, 2, 1, 3)
        out = out.reshape(self.nblocks * self.block_r, self.nnb * self.bn)
        return out[: self.nrows, : self.ncols]


# ---------------------------------------------------------------------------
# Host → device conversions
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def csr_from_host(h: HostCSR, nnz_cap: int | None = None,
                  dtype=jnp.float32) -> CSR:
    cap = _round_up(max(h.nnz, 1), 8) if nnz_cap is None else nnz_cap
    if cap < h.nnz:
        raise ValueError(f"nnz_cap {cap} < nnz {h.nnz}")
    indices = np.full(cap, h.ncols, dtype=np.int32)
    data = np.zeros(cap, dtype=np.float32)
    indices[: h.nnz] = h.indices
    data[: h.nnz] = h.data
    return CSR(indptr=jnp.asarray(h.indptr, jnp.int32),
               indices=jnp.asarray(indices),
               data=jnp.asarray(data, dtype),
               nrows=h.nrows, ncols=h.ncols)


def csr_cluster_from_host(h: HostCSR, boundaries: Sequence[int],
                          max_cluster: int, slot_cap: int | None = None,
                          dtype=jnp.float32) -> CSRCluster:
    """Build CSR_Cluster from consecutive-row clusters.

    ``boundaries`` — cluster start rows, ending sentinel nrows implied.

    Vectorized: one searchsorted maps every nonzero to its cluster, one
    argsort over the (cluster, column) key discovers the deduplicated
    column slots, and the whole value slab fills with a single
    fancy-indexed assignment at (slot, row − row_base). Identical layout
    to :func:`csr_cluster_from_host_reference`.
    """
    bounds = np.asarray(list(boundaries) + [h.nrows], dtype=np.int64)
    ncl = bounds.shape[0] - 1
    sizes = np.diff(bounds)
    over = sizes > max_cluster
    if over.any():
        raise ValueError(f"cluster {int(np.argmax(over))} larger than "
                         "max_cluster")
    row_base = bounds[:-1].astype(np.int32)
    csize = sizes.astype(np.int32)

    rows = expand_indptr(h.indptr)
    cols = h.indices.astype(np.int64)
    cl = np.searchsorted(bounds, rows, side="right") - 1
    key = cl * max(h.ncols, 1) + cols
    order = np.argsort(key, kind="stable")
    skey = key[order]
    first = boundary_mask(skey)
    slot_sorted = np.cumsum(first) - 1          # slot id per sorted nnz
    ukey = skey[first]                          # one key per (cluster, col)
    per_cluster = segmented_count(ukey // max(h.ncols, 1), ncl)
    ptr = np.zeros(ncl + 1, dtype=np.int64)
    np.cumsum(per_cluster, out=ptr[1:])
    total = int(ptr[-1])
    cap = _round_up(max(total, 1), 8) if slot_cap is None else slot_cap
    if cap < total:
        raise ValueError(f"slot_cap {cap} < required {total}")
    cols_out = np.full(cap, h.ncols, dtype=np.int32)
    values = np.zeros((cap, max_cluster), dtype=np.float32)
    if total:
        cols_out[:total] = (ukey % max(h.ncols, 1)).astype(np.int32)
        slot = np.empty(h.nnz, dtype=np.int64)
        slot[order] = slot_sorted
        values[slot, rows - bounds[cl]] = h.data
    return CSRCluster(
        cluster_ptr=jnp.asarray(ptr.astype(np.int32)),
        cols=jnp.asarray(cols_out),
        values=jnp.asarray(values, dtype),
        row_base=jnp.asarray(row_base),
        cluster_size=jnp.asarray(csize),
        nrows=h.nrows, ncols=h.ncols, max_cluster=max_cluster)


def csr_cluster_from_host_reference(h: HostCSR, boundaries: Sequence[int],
                                    max_cluster: int,
                                    slot_cap: int | None = None,
                                    dtype=jnp.float32) -> CSRCluster:
    """Loop reference for :func:`csr_cluster_from_host` (test oracle)."""
    bounds = list(boundaries) + [h.nrows]
    ncl = len(bounds) - 1
    ptr = [0]
    cols_l: list[np.ndarray] = []
    vals_l: list[np.ndarray] = []
    row_base = np.zeros(ncl, dtype=np.int32)
    csize = np.zeros(ncl, dtype=np.int32)
    for c in range(ncl):
        lo, hi = bounds[c], bounds[c + 1]
        if hi - lo > max_cluster:
            raise ValueError(f"cluster {c} larger than max_cluster")
        row_base[c] = lo
        csize[c] = hi - lo
        merged = np.unique(np.concatenate(
            [h.row(i)[0] for i in range(lo, hi)] or
            [np.empty(0, np.int32)]))
        slab = np.zeros((merged.size, max_cluster), dtype=np.float32)
        for k, i in enumerate(range(lo, hi)):
            ci, vi = h.row(i)
            pos = np.searchsorted(merged, ci)
            slab[pos, k] = vi
        cols_l.append(merged.astype(np.int32))
        vals_l.append(slab)
        ptr.append(ptr[-1] + merged.size)
    total = ptr[-1]
    cap = _round_up(max(total, 1), 8) if slot_cap is None else slot_cap
    if cap < total:
        raise ValueError(f"slot_cap {cap} < required {total}")
    cols = np.full(cap, h.ncols, dtype=np.int32)
    values = np.zeros((cap, max_cluster), dtype=np.float32)
    if total:
        cols[:total] = np.concatenate(cols_l)
        values[:total] = np.concatenate(vals_l, axis=0)
    return CSRCluster(
        cluster_ptr=jnp.asarray(np.asarray(ptr, np.int32)),
        cols=jnp.asarray(cols),
        values=jnp.asarray(values, dtype),
        row_base=jnp.asarray(row_base),
        cluster_size=jnp.asarray(csize),
        nrows=h.nrows, ncols=h.ncols, max_cluster=max_cluster)


def bcc_from_host(h: HostCSR, block_r: int = 8, block_k: int = 128,
                  tiles_per_block: int | None = None,
                  dtype=jnp.float32) -> BCC:
    """Pack a (reordered) HostCSR into BCC tiles.

    Vectorized: per-block tile discovery is one argsort over the
    ``block_id * nk + col // block_k`` key (:func:`bcc_layout`); slab fill
    is one fancy-indexed assignment at each nonzero's lattice position.
    Identical layout to :func:`bcc_from_host_reference`.
    """
    tile_ids, ntiles, tpb, pos = bcc_layout(h, block_r, block_k,
                                            tiles_per_block)
    values = np.zeros((ntiles.shape[0] * tpb, block_r, block_k),
                      dtype=np.float32)
    values.reshape(-1)[pos] = h.data
    # imported here: repro.obs, which transfer uses, imports this module
    from repro.core.transfer import to_device
    tile_ids, values, ntiles = to_device(tile_ids, values, ntiles,
                                         dtypes=(None, dtype, None))
    return BCC(tile_ids=tile_ids, values=values, ntiles=ntiles,
               nrows=h.nrows, ncols=h.ncols,
               block_r=block_r, block_k=block_k, tiles_per_block=tpb)


def bcc_layout(h: HostCSR, block_r: int = 8, block_k: int = 128,
               tiles_per_block: int | None = None
               ) -> tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """The value-free part of :func:`bcc_from_host`, on the host:
    ``(tile_ids, ntiles, tiles_per_block, pos)``, where ``pos[i]`` is the
    flat index of nonzero ``i`` in the ``(nblocks * tiles_per_block,
    block_r, block_k)`` value lattice."""
    nb = (h.nrows + block_r - 1) // block_r
    nk = (h.ncols + block_k - 1) // block_k
    rows = expand_indptr(h.indptr)
    cols = h.indices.astype(np.int64)
    key = (rows // block_r) * nk + cols // block_k
    order = np.argsort(key, kind="stable")
    skey = key[order]
    first = boundary_mask(skey)
    slot_sorted = np.cumsum(first) - 1          # live-tile id per sorted nnz
    ukey = skey[first]
    ublk = ukey // nk                           # block of each live tile
    per_block = segmented_count(ublk, nb)       # live tiles per block
    max_live = max(1, int(per_block.max()) if nb else 1)
    tpb = max_live if tiles_per_block is None else tiles_per_block
    if tpb < max_live:
        raise ValueError(f"tiles_per_block {tpb} < max live {max_live}")
    # padded flat position of each live tile: block * tpb + rank-in-block
    offs = np.zeros(nb, dtype=np.int64)
    np.cumsum(per_block[:-1], out=offs[1:])
    rank = np.arange(ublk.shape[0], dtype=np.int64) - offs[ublk]
    flat = ublk * tpb + rank
    tile_ids = np.zeros(nb * tpb, dtype=np.int32)
    tile_ids[flat] = (ukey % nk).astype(np.int32)
    nnz_flat = np.empty(h.nnz, dtype=np.int64)
    nnz_flat[order] = flat[slot_sorted]
    pos = (nnz_flat * block_r + rows % block_r) * block_k + cols % block_k
    return tile_ids, per_block.astype(np.int32), tpb, pos


def bcc_from_host_reference(h: HostCSR, block_r: int = 8, block_k: int = 128,
                            tiles_per_block: int | None = None,
                            dtype=jnp.float32) -> BCC:
    """Loop reference for :func:`bcc_from_host` (test oracle)."""
    nb = (h.nrows + block_r - 1) // block_r
    per_block_tiles: list[np.ndarray] = []
    per_block_slabs: list[np.ndarray] = []
    max_live = 1
    for b in range(nb):
        lo, hi = b * block_r, min((b + 1) * block_r, h.nrows)
        # active column tiles of this row block
        cols = np.concatenate([h.row(i)[0] for i in range(lo, hi)]
                              or [np.empty(0, np.int32)])
        tiles = np.unique(cols // block_k) if cols.size else np.empty(0, np.int64)
        slabs = np.zeros((tiles.size, block_r, block_k), dtype=np.float32)
        tpos = {int(t): s for s, t in enumerate(tiles)}
        for r, i in enumerate(range(lo, hi)):
            ci, vi = h.row(i)
            for c, v in zip(ci, vi):
                t = int(c) // block_k
                slabs[tpos[t], r, int(c) % block_k] = v
        per_block_tiles.append(tiles.astype(np.int32))
        per_block_slabs.append(slabs)
        max_live = max(max_live, tiles.size)
    tpb = max_live if tiles_per_block is None else tiles_per_block
    if tpb < max_live:
        raise ValueError(f"tiles_per_block {tpb} < max live {max_live}")
    tile_ids = np.zeros(nb * tpb, dtype=np.int32)
    values = np.zeros((nb * tpb, block_r, block_k), dtype=np.float32)
    ntiles = np.zeros(nb, dtype=np.int32)
    for b in range(nb):
        n = per_block_tiles[b].size
        ntiles[b] = n
        tile_ids[b * tpb: b * tpb + n] = per_block_tiles[b]
        values[b * tpb: b * tpb + n] = per_block_slabs[b]
    return BCC(tile_ids=jnp.asarray(tile_ids),
               values=jnp.asarray(values, dtype),
               ntiles=jnp.asarray(ntiles),
               nrows=h.nrows, ncols=h.ncols,
               block_r=block_r, block_k=block_k, tiles_per_block=tpb)


def tiled_csr_from_host(h: HostCSR, block_k: int = 128, bn: int = 128,
                        tile_cap: int | None = None,
                        dtype=jnp.float32) -> TiledCSR:
    """Pack a HostCSR into the tiled-sparse device format.

    Vectorized: live-tile discovery is one argsort over the
    ``(row // block_k) * nnb + col // bn`` key; the table is one
    :func:`repro.core.segment.key_table` scatter (``base=1`` — slot 0 is
    the reserved zero tile) (:func:`tiled_layout`); the slab fill is one
    fancy-indexed assignment at each nonzero's tile-store position.
    Identical layout to :func:`tiled_csr_from_host_reference`.
    """
    table, cap, pos = tiled_layout(h, block_k, bn, tile_cap)
    tiles = np.zeros((cap, block_k, bn), dtype=np.float32)
    tiles.reshape(-1)[pos] = h.data
    from repro.core.transfer import to_device
    tiles, table = to_device(tiles, table, dtypes=(dtype, None))
    return TiledCSR(tiles=tiles, table=table,
                    nrows=h.nrows, ncols=h.ncols, block_k=block_k, bn=bn)


def tiled_layout(h: HostCSR, block_k: int = 128, bn: int = 128,
                 tile_cap: int | None = None
                 ) -> tuple[np.ndarray, int, np.ndarray]:
    """The value-free part of :func:`tiled_csr_from_host`, on the host:
    ``(table, tile_cap, pos)``, where ``pos[i]`` is the flat index of
    nonzero ``i`` in the ``(tile_cap, block_k, bn)`` tile store."""
    nkb = (h.nrows + block_k - 1) // block_k
    nnb = (h.ncols + bn - 1) // bn
    rows = expand_indptr(h.indptr)
    cols = h.indices.astype(np.int64)
    key = (rows // block_k) * nnb + cols // bn
    order = np.argsort(key, kind="stable")
    skey = key[order]
    first = boundary_mask(skey)
    slot_sorted = np.cumsum(first)              # live-tile slot (1-based)
    ukey = skey[first]
    nlive = int(ukey.shape[0])
    cap = nlive + 1 if tile_cap is None else tile_cap
    if cap < nlive + 1:
        raise ValueError(f"tile_cap {cap} < live tiles + zero tile "
                         f"{nlive + 1}")
    table = key_table(ukey, nkb * nnb, base=1)
    slot = np.empty(h.nnz, dtype=np.int64)
    slot[order] = slot_sorted
    return table, cap, (slot * block_k + rows % block_k) * bn + cols % bn


def scatter_map(pos: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` of a value fill ``out.flat[pos] = data``, one pair per
    distinct position: ``dst`` ascending, and ``src`` the nonzero whose
    value stays there, the last one written as numpy's assignment keeps
    it. ``out.flat[dst] = data[src]`` then writes each position once.

    >>> src, dst = scatter_map(np.array([5, 2, 5]))
    >>> src.tolist(), dst.tolist()
    ([1, 2], [2, 5])
    """
    order = np.argsort(pos, kind="stable")
    spos = pos[order]
    last = np.ones(spos.shape[0], dtype=bool)
    last[:-1] = spos[1:] != spos[:-1]
    return order[last], spos[last]


def tiled_csr_from_host_reference(h: HostCSR, block_k: int = 128,
                                  bn: int = 128,
                                  tile_cap: int | None = None,
                                  dtype=jnp.float32) -> TiledCSR:
    """Loop reference for :func:`tiled_csr_from_host` (test oracle)."""
    nkb = (h.nrows + block_k - 1) // block_k
    nnb = (h.ncols + bn - 1) // bn
    live: dict[tuple[int, int], int] = {}
    slabs: list[np.ndarray] = []
    for i in range(h.nrows):
        ci, vi = h.row(i)
        for c, v in zip(ci, vi):
            tk = (i // block_k, int(c) // bn)
            if tk not in live:
                live[tk] = len(slabs) + 1
                slabs.append(np.zeros((block_k, bn), dtype=np.float32))
            slabs[live[tk] - 1][i % block_k, int(c) % bn] = v
    # the vectorized packer enumerates tiles in sorted key order
    order = sorted(live, key=lambda t: t[0] * nnb + t[1])
    nlive = len(order)
    cap = nlive + 1 if tile_cap is None else tile_cap
    if cap < nlive + 1:
        raise ValueError(f"tile_cap {cap} < live tiles + zero tile "
                         f"{nlive + 1}")
    table = np.zeros(nkb * nnb, dtype=np.int32)
    tiles = np.zeros((cap, block_k, bn), dtype=np.float32)
    for s, tk in enumerate(order):
        table[tk[0] * nnb + tk[1]] = s + 1
        tiles[s + 1] = slabs[live[tk] - 1]
    return TiledCSR(tiles=jnp.asarray(tiles, dtype),
                    table=jnp.asarray(table),
                    nrows=h.nrows, ncols=h.ncols, block_k=block_k, bn=bn)


def tiled_live_tiles(h: HostCSR, block_k: int = 128, bn: int = 128) -> int:
    """Number of live ``(block_k, bn)`` tiles of ``h`` — the analytic
    footprint counter (no tile materialization): the tiled kernel streams
    exactly this many dense tiles of B into VMEM.

    >>> tiled_live_tiles(HostCSR.from_dense(np.eye(256, dtype=np.float32)),
    ...                  128, 128)
    2
    """
    if h.nnz == 0:
        return 0
    rows = expand_indptr(h.indptr)
    nnb = (h.ncols + bn - 1) // bn
    key = (rows // block_k) * nnb + h.indices.astype(np.int64) // bn
    return int(np.unique(key).size)


def select_block_k(h: HostCSR, *, bn: int = 128,
                   candidates: Sequence[int] = (128, 256, 512),
                   step_overhead_bytes: int = 6144) -> int:
    """Heuristic k-tile height for the tiled Sp×Sp path.

    The trade-off (ROADMAP's adaptive ``block_k`` item): taller tiles merge
    k-adjacent live tiles — fewer grid steps and fewer A-slab fetches per
    contraction — but dilute live-tile fill, inflating B's streamed bytes.
    Score each candidate by its B footprint plus a per-live-tile step cost
    (one A slab DMA + grid-step overhead, ``step_overhead_bytes`` in byte
    units) and keep the cheapest. All candidates are lane-aligned multiples
    of 128 so the A slab (whose *lane* dimension is ``block_k``) stays
    MXU-tileable; 128 wins whenever fill is low (``features.tile128_fill``
    is the planner-facing proxy of the same quantity).

    >>> select_block_k(HostCSR.from_dense(np.eye(256, dtype=np.float32)))
    128
    >>> select_block_k(HostCSR.from_dense(np.ones((512, 512), np.float32)))
    512
    """
    best_bk, best_score = None, None
    for bk in candidates:
        if bk % 128:
            raise ValueError(f"block_k {bk} not a multiple of 128")
        live = tiled_live_tiles(h, bk, bn)
        score = live * bk * bn * 4 + live * step_overhead_bytes
        if best_score is None or score < best_score:
            best_bk, best_score = bk, score
    return int(best_bk)


def block_r_counts(h: HostCSR, table, *, block_k: int, nnb: int,
                   heights: Sequence[int] = (8, 16, 32, 64, 128),
                   mask: HostCSR | None = None, bn: int = 128
                   ) -> dict[int, tuple[int, ...]]:
    """The stream lengths a pack of ``h @ B`` would build at each row-block
    height of ``heights``, counted without building one: ``{r: (steps,
    pairs, windows)}``.

    ``steps`` is the length of A's compact stream (cover steps of empty
    blocks and tail padding included), ``pairs`` that of its
    :func:`live_pair_stream` against B's tile ``table`` (``block_k`` rows
    by ``nnb`` column tiles; sentinels and tail padding included), and
    ``windows`` the live ``(r, bn)`` windows of C. Every height is a
    multiple of the first: one sort of A's live (block, k-tile) keys at
    the first height gives them all, a block at height r being ``r /
    heights[0]`` consecutive blocks of the first.

    With a ``mask`` (the product's shape, B's tiles ``bn`` wide), the
    counts are those of the masked stream: only the pairs whose ``(r,
    bn)`` window of C holds a nonzero of the mask
    (:func:`mask_windows`, the ``window_live`` of
    :func:`live_pair_stream`), and the windows they reach; each entry
    then ends with the unmasked stream's ``pairs`` at that height:
    ``{r: (steps, pairs, windows, pairs_unmasked)}``.

    >>> h = HostCSR.from_dense(np.ones((32, 32), np.float32))
    >>> block_r_counts(h, np.ones(2, np.int32), block_k=16, nnb=1,
    ...                heights=(8, 16))
    {8: (8, 8, 4), 16: (8, 8, 2)}
    """
    base = heights[0]
    nk = (h.ncols + block_k - 1) // block_k
    key = ((expand_indptr(h.indptr) // base) * nk
           + h.indices.astype(np.int64) // block_k)
    key = np.unique(key[boundary_mask(key)])     # a row's runs first
    tbl = np.asarray(table).reshape(-1, nnb) > 0
    per_row = tbl.sum(axis=1)                    # B's live tiles per k-row
    row_start = np.concatenate([[0], np.cumsum(per_row)[:-1]])
    tile_j = np.nonzero(tbl)[1]                  # their column tiles
    out = {}
    for r in heights:
        nb = (h.nrows + r - 1) // r
        coarse = np.unique(key // nk // (r // base) * nk + key % nk)
        blk, kt = coarse // nk, coarse % nk
        n = per_row[kt]
        steps = coarse.size + nb - np.unique(blk).size
        pairs = int(n.sum()) + nb - np.unique(blk[n > 0]).size
        # one (block, j) per live pair: B's tile row kt, for each key
        first = np.repeat(row_start[kt] - (np.cumsum(n) - n), n)
        j = tile_j[first + np.arange(first.size)]
        wkey = np.repeat(blk, n) * nnb + j
        unmasked = ()
        if mask is not None:
            unmasked = (_round_up(pairs, 8),)
            wkey = wkey[mask_windows(mask, block_r=r, bn=bn, nblocks=nb,
                                     nnb=nnb)[wkey]]
            pairs = wkey.size + nb - np.unique(wkey // nnb).size
        windows = np.unique(wkey).size
        out[r] = (_round_up(max(steps, 1), 8), _round_up(pairs, 8),
                  windows, *unmasked)
    return out


def mask_windows(mask: HostCSR, *, block_r: int, bn: int, nblocks: int,
                 nnb: int) -> np.ndarray:
    """``(nblocks * nnb,)`` bool: the ``(block_r, bn)`` windows of C that
    hold a nonzero of ``mask``, window ``blk * nnb + j``. A masked
    product computes only these (``live_pair_stream``'s
    ``window_live``)."""
    live = np.zeros(nblocks * nnb, dtype=bool)
    live[expand_indptr(mask.indptr) // block_r * nnb
         + mask.indices.astype(np.int64) // bn] = True
    return live


# ---------------------------------------------------------------------------
# live-pair compacted grid (the Sp×Sp kernel's sparsity-compacted stream)
# ---------------------------------------------------------------------------


def live_pair_stream(block_ids, tile_ids, table, *, nnb: int, nblocks: int,
                     step_live=None, window_live=None, pad_to: int = 8
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Intersect A's compact (block, k-tile) stream with B's tile table.

    The PR-3 kernels walk a dense ``(nnb, S)`` grid — every (stream step,
    column strip) pair costs a grid step and an A-slab DMA even when B's
    tile there is dead. This builder emits only the *live* pairs::

        slot[s, j] = table[tile_ids[s] * nnb + j]  > 0

    ordered by (block, s, j) — so each output row strip's accumulation
    runs are consecutive (one C write-back per block) and pairs sharing a
    stream step are adjacent (Pallas elides the repeated A-slab DMA: A is
    fetched once per stream step total, not ``nnb`` times).

    Every block with no live pair still gets one zero-slot sentinel at its
    first stream step — the ``cover_all_blocks`` convention carried to the
    pair grid, so the kernel zero-initializes every C strip it owns. The
    stream is tail-padded to a multiple of ``pad_to`` with zero-slot
    repeats of the last pair (same block → no re-init, slot 0 → no MXU).

    Args:
      block_ids / tile_ids: the (S,)-shaped compact A stream
        (``bcc_compact_stream(a, cover_all_blocks=True)``) — every block
        in ``range(nblocks)`` must appear.
      table: B's flat (nkb * nnb,) tile table (0 = dead).
      step_live: optional (S,) bool — False marks synthetic stream steps
        (``cover_all_blocks`` zero slabs, tail padding) whose pairs would
        multiply a zero A slab; they are dropped from the pair stream.
      window_live: optional (nblocks * nnb,) bool — False marks C windows
        ``(block, j)`` that no one reads (a masked product's windows
        with no nonzero of the mask, :func:`mask_windows`); their pairs
        are dropped too, and a block left with none gets its sentinel.

    Returns ``(blocks, js, slots, a_idx)`` int32 arrays of equal length:
    output strip, column strip, B tile slot (0 = no MXU issue) and A
    stream index of each grid step.
    """
    block_ids = np.asarray(block_ids, dtype=np.int64)
    tile_ids = np.asarray(tile_ids, dtype=np.int64)
    table = np.asarray(table, dtype=np.int32)
    s_total = block_ids.shape[0]
    if step_live is None:
        step_live = np.ones(s_total, dtype=bool)
    step_live = np.asarray(step_live, dtype=bool)
    tbl = table.reshape(-1, nnb)
    wins = (None if window_live is None
            else np.asarray(window_live, dtype=bool).reshape(nblocks, nnb))
    # chunked intersection: the dense (S, nnb) expansion is exactly the
    # padded-grid footprint this builder exists to kill — bound the
    # transient to ~16 MiB of int32 per chunk, concatenating only the
    # live pairs (chunks are s-ascending, so (s, j) order is preserved)
    chunk = max(1, (1 << 22) // max(nnb, 1))
    s_parts, j_parts, slot_parts = [], [], []
    for lo in range(0, s_total, chunk):
        hi = min(lo + chunk, s_total)
        slots_c = tbl[tile_ids[lo:hi]]                    # (chunk, nnb)
        live_c = (slots_c > 0) & step_live[lo:hi, None]
        if wins is not None:
            live_c &= wins[block_ids[lo:hi]]
        sc, jc = np.nonzero(live_c)      # row-major: (s, j) ascending
        s_parts.append(sc + lo)
        j_parts.append(jc)
        slot_parts.append(slots_c[sc, jc].astype(np.int64))
    s_idx = (np.concatenate(s_parts) if s_parts
             else np.empty(0, np.int64))
    j_idx = (np.concatenate(j_parts) if j_parts
             else np.empty(0, np.int64))
    slot_vals = (np.concatenate(slot_parts) if slot_parts
                 else np.empty(0, np.int64))
    # first stream step of every block (sentinel anchor)
    first = boundary_mask(block_ids)
    first_step = np.full(nblocks, -1, dtype=np.int64)
    first_step[block_ids[first]] = np.flatnonzero(first)
    covered = np.zeros(nblocks, dtype=bool)
    covered[block_ids[s_idx]] = True
    missing = np.flatnonzero(~covered)
    if missing.size and (first_step[missing] < 0).any():
        raise ValueError("stream must cover every block "
                         "(use cover_all_blocks=True)")
    sen_s = first_step[missing]
    # merge live pairs and sentinels in (s, j) order — block order follows
    # because block_ids is non-decreasing; sentinels take j = 0 and cannot
    # collide with a live (s, 0) pair (their block has no live pair at all)
    a_s = np.concatenate([s_idx, sen_s])
    a_j = np.concatenate([j_idx, np.zeros(sen_s.size, dtype=np.int64)])
    a_slot = np.concatenate([slot_vals,
                             np.zeros(sen_s.size, dtype=np.int64)])
    order = np.argsort(a_s * nnb + a_j, kind="stable")
    a_s, a_j, a_slot = a_s[order], a_j[order], a_slot[order]
    pad = (-a_s.size) % pad_to
    if pad:
        a_s = np.concatenate([a_s, np.repeat(a_s[-1], pad)])
        a_j = np.concatenate([a_j, np.repeat(a_j[-1], pad)])
        a_slot = np.concatenate([a_slot, np.zeros(pad, dtype=np.int64)])
    return (block_ids[a_s].astype(np.int32), a_j.astype(np.int32),
            a_slot.astype(np.int32), a_s.astype(np.int32))


def live_pair_stream_reference(block_ids, tile_ids, table, *, nnb: int,
                               nblocks: int, step_live=None,
                               window_live=None, pad_to: int = 8
                               ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                          np.ndarray]:
    """Loop reference for :func:`live_pair_stream` (test oracle)."""
    block_ids = np.asarray(block_ids, dtype=np.int64)
    tile_ids = np.asarray(tile_ids, dtype=np.int64)
    table = np.asarray(table, dtype=np.int64)
    s_total = block_ids.shape[0]
    if step_live is None:
        step_live = np.ones(s_total, dtype=bool)
    blocks, js, slots, a_idx = [], [], [], []
    pair_blocks = set()
    for s in range(s_total):
        if not step_live[s]:
            continue
        for j in range(nnb):
            slot = int(table[int(tile_ids[s]) * nnb + j])
            if window_live is not None and not window_live[
                    int(block_ids[s]) * nnb + j]:
                continue
            if slot > 0:
                blocks.append(int(block_ids[s]))
                js.append(j)
                slots.append(slot)
                a_idx.append(s)
                pair_blocks.add(int(block_ids[s]))
    # sentinel per pair-less block, at the block's first stream step
    for b in range(nblocks):
        if b in pair_blocks:
            continue
        for s in range(s_total):
            if int(block_ids[s]) == b:
                blocks.append(b)
                js.append(0)
                slots.append(0)
                a_idx.append(s)
                break
        else:
            raise ValueError("stream must cover every block "
                             "(use cover_all_blocks=True)")
    order = np.argsort(np.asarray(a_idx, dtype=np.int64) * nnb
                       + np.asarray(js, dtype=np.int64), kind="stable")
    blocks = [blocks[i] for i in order]
    js = [js[i] for i in order]
    slots = [slots[i] for i in order]
    a_idx = [a_idx[i] for i in order]
    pad = (-len(blocks)) % pad_to
    for _ in range(pad):
        blocks.append(blocks[-1])
        js.append(js[-1])
        slots.append(0)
        a_idx.append(a_idx[-1])
    return (np.asarray(blocks, np.int32), np.asarray(js, np.int32),
            np.asarray(slots, np.int32), np.asarray(a_idx, np.int32))


# the single source of truth for counter units: every counter emitted by
# :func:`live_pair_counters` (and printed by ``benchmarks/bench_kernels``)
# is listed here with the unit its value is expressed in. Counts of DMAs
# are *events* (tiles / slabs fetched), ``*_bytes`` counters are HBM bytes,
# and ``steps_per_mxu`` is a dimensionless ratio — the counters glossary in
# ``docs/kernels.md`` renders this table and ``make docs-check`` asserts
# the two stay in sync.
COUNTER_UNITS = {
    "grid_steps": "grid steps (count)",
    "mxu_issues": "MXU contractions (count)",
    "a_fetches": "A slab DMAs after elision (count)",
    "a_bytes": "A slab HBM traffic (bytes)",
    "steps_per_mxu": "grid steps per MXU issue (ratio)",
    "b_tile_fetches": "live B tile DMAs after elision (count)",
    "b_tile_refetches": "live B tile DMAs beyond the first per tile (count)",
    "b_distinct_tiles": "distinct live B tiles touched (count)",
    "b_bytes": "live B tile HBM traffic (bytes)",
    "c_nnz": "C nonzeros (count)",
    "c_bytes_dense": "dense C row-strip HBM writes (bytes)",
    "c_bytes_sparse": "CompactedC live-slab HBM writes (bytes)",
    "c_compaction_steps": "sparse-C compaction windows written (count)",
}


def live_pair_counters(pairs, *, block_r: int, block_k: int,
                       bn: int | None = None, value_bytes: int = 4) -> dict:
    """Traffic counters of a live-pair stream (the benchmark's gated
    metrics). Units are per :data:`COUNTER_UNITS` — DMA counters count
    *fetch events* after the Pallas elision (consecutive grid steps
    sharing an index fetch once), ``*_bytes`` counters are HBM bytes.

    * ``a_fetches`` / ``a_bytes`` — A slab traffic: one fetch per run of
      equal A stream indices.
    * ``b_tile_fetches`` — live B tile traffic of the *streamed* kernels:
      one fetch per run of equal (live) slots. ``b_tile_refetches`` is the
      excess over fetching each distinct tile once. ``b_bytes`` needs
      ``bn`` (the tile width) and is omitted when it is not given.

    >>> blocks = [0, 0, 1, 1]; js = [0, 1, 0, 1]
    >>> slots  = [3, 5, 3, 5]; a_idx = [0, 0, 2, 2]
    >>> c = live_pair_counters((blocks, js, slots, a_idx),
    ...                        block_r=8, block_k=16, bn=16)
    >>> c["grid_steps"], c["mxu_issues"], c["a_fetches"]
    (4, 4, 2)
    >>> c["b_tile_fetches"], c["b_distinct_tiles"], c["b_tile_refetches"]
    (4, 2, 2)
    >>> c["a_bytes"] == 2 * 8 * 16 * 4 and c["b_bytes"] == 4 * 16 * 16 * 4
    True
    """
    blocks, js, slots, a_idx = (np.asarray(p) for p in pairs)
    grid_steps = int(a_idx.shape[0])
    mxu_issues = int((slots > 0).sum())
    a_fetches = int(boundary_mask(a_idx).sum()) if grid_steps else 0
    live = slots > 0
    b_fetches = int((boundary_mask(slots) & live).sum()) if grid_steps else 0
    b_distinct = int(np.unique(slots[live]).size)
    out = {
        "grid_steps": grid_steps,
        "mxu_issues": mxu_issues,
        "a_fetches": a_fetches,
        "a_bytes": a_fetches * block_r * block_k * value_bytes,
        "steps_per_mxu": grid_steps / max(mxu_issues, 1),
        "b_tile_fetches": b_fetches,
        "b_tile_refetches": b_fetches - b_distinct,
        "b_distinct_tiles": b_distinct,
    }
    if bn is not None:
        out["b_bytes"] = b_fetches * block_k * bn * value_bytes
    return out


# ---------------------------------------------------------------------------
# multi-core sharding of the pair stream
# ---------------------------------------------------------------------------


def partition_pair_stream(pairs, *, nblocks: int, num_shards: int,
                          pad_to: int = 8
                          ) -> tuple[np.ndarray, list[tuple]]:
    """Split a live-pair stream into per-core contiguous block ranges.

    Row blocks own disjoint C row strips, so a partition at block
    boundaries needs no cross-core accumulation — each core runs its
    sub-stream against its own strip range. Balance is by per-block
    *live*-pair counts (slot > 0 — the MXU work; zero-slot sentinels and
    tail pads are free steps, excluded from the weights): boundary ``i``
    lands where the cumulative live-pair count is closest to
    ``i × total / num_shards`` (greedy bin-pack over the per-block prefix
    sums; ties take the earlier block, and every shard keeps at least
    one block). The stream must be block-sorted and cover
    every block (the :func:`live_pair_stream` contract — pair-less blocks
    travel with their zero-slot sentinel, so each lands in exactly one
    shard).

    Returns ``(ranges, shard_pairs)``: ``ranges`` is ``(S, 2)`` int64
    ``[start, end)`` block ranges covering ``0..nblocks`` (``S`` =
    ``min(num_shards, nblocks)``), and ``shard_pairs[i]`` is the i-th
    shard's ``(blocks, js, slots, a_idx)`` sub-stream, tail-padded to a
    multiple of ``pad_to`` with zero-slot repeats of its last pair. With
    ``num_shards=1`` the single shard is the input stream, bitwise.

    >>> blocks = [0, 0, 0, 1, 2, 2, 3, 3]; js = [0, 1, 2, 0, 0, 1, 0, 1]
    >>> slots  = [1, 2, 3, 4, 5, 6, 7, 8]; a_idx = [0, 0, 0, 1, 2, 2, 3, 3]
    >>> ranges, shards = partition_pair_stream(
    ...     (blocks, js, slots, a_idx), nblocks=4, num_shards=2, pad_to=1)
    >>> ranges.tolist()
    [[0, 2], [2, 4]]
    >>> shards[1][0].tolist()                    # second shard's blocks
    [2, 2, 3, 3]
    """
    blocks, js, slots, a_idx = (np.asarray(p) for p in pairs)
    if blocks.size and np.any(np.diff(blocks) < 0):
        raise ValueError("pair stream must be block-sorted")
    counts = np.bincount(blocks[slots > 0],
                         minlength=nblocks).astype(np.int64)
    cum = np.zeros(nblocks + 1, dtype=np.int64)
    np.cumsum(counts, out=cum[1:])
    total = int(cum[-1])
    s_eff = max(1, min(int(num_shards), nblocks))
    bounds = [0]
    for i in range(1, s_eff):
        target = total * i / s_eff
        e0 = int(np.clip(np.searchsorted(cum, target, side="left"),
                         1, nblocks))
        e = e0 - 1 if target - cum[e0 - 1] <= cum[e0] - target else e0
        # ties take the earliest block: the first boundary with this
        # cumulative count (blocks without live pairs leave it flat)
        e = int(np.searchsorted(cum, cum[e], side="left"))
        e = int(np.clip(e, bounds[-1] + 1, nblocks - (s_eff - i)))
        bounds.append(e)
    bounds.append(nblocks)
    ranges = np.stack([np.asarray(bounds[:-1], np.int64),
                       np.asarray(bounds[1:], np.int64)], axis=1)
    shard_pairs = []
    for start, end in ranges:
        lo = int(np.searchsorted(blocks, start, side="left"))
        hi = int(np.searchsorted(blocks, end, side="left"))
        sb, sj, ss, sa = (arr[lo:hi] for arr in (blocks, js, slots, a_idx))
        pad = (-sb.size) % pad_to
        if pad:
            sb = np.concatenate([sb, np.repeat(sb[-1], pad)])
            sj = np.concatenate([sj, np.repeat(sj[-1], pad)])
            ss = np.concatenate([ss, np.zeros(pad, ss.dtype)])
            sa = np.concatenate([sa, np.repeat(sa[-1], pad)])
        shard_pairs.append((sb, sj, ss, sa))
    return ranges, shard_pairs


def partition_pair_stream_reference(pairs, *, nblocks: int, num_shards: int,
                                    pad_to: int = 8
                                    ) -> tuple[np.ndarray, list[tuple]]:
    """Loop reference for :func:`partition_pair_stream` (test oracle)."""
    blocks, js, slots, a_idx = (np.asarray(p) for p in pairs)
    counts = [0] * nblocks
    for b, s in zip(blocks.tolist(), slots.tolist()):
        if s > 0:                              # live pairs only (the MXU
            counts[b] += 1                     # work being balanced)
    total = sum(counts)
    s_eff = max(1, min(int(num_shards), nblocks))
    cum = [0]
    for c in counts:
        cum.append(cum[-1] + c)
    bounds = [0]
    for i in range(1, s_eff):
        target = total * i / s_eff
        best_e, best_d = None, None
        for e in range(nblocks + 1):           # argmin |cum[e] - target|,
            d = abs(cum[e] - target)           # ties to the smaller e
            if best_d is None or d < best_d:
                best_e, best_d = e, d
        e = min(max(best_e, bounds[-1] + 1), nblocks - (s_eff - i))
        bounds.append(e)
    bounds.append(nblocks)
    ranges = np.asarray([[bounds[i], bounds[i + 1]]
                         for i in range(s_eff)], dtype=np.int64)
    shard_pairs = []
    for start, end in ranges:
        keep = [t for t in range(blocks.shape[0])
                if start <= blocks[t] < end]
        sb = [int(blocks[t]) for t in keep]
        sj = [int(js[t]) for t in keep]
        ss = [int(slots[t]) for t in keep]
        sa = [int(a_idx[t]) for t in keep]
        while len(sb) % pad_to:
            sb.append(sb[-1])
            sj.append(sj[-1])
            ss.append(0)
            sa.append(sa[-1])
        shard_pairs.append((np.asarray(sb, blocks.dtype),
                            np.asarray(sj, js.dtype),
                            np.asarray(ss, slots.dtype),
                            np.asarray(sa, a_idx.dtype)))
    return ranges, shard_pairs


def partition_balance(shard_pairs) -> float:
    """Worst-shard imbalance of a partition: max per-shard live-pair count
    over the ideal (total ÷ shards). 1.0 is a perfect split; the
    ``bench_kernels`` acceptance gate requires ≤ 1.2 (within 20% of
    ideal) on the quick-tier families.

    >>> even = [(0, 0, [1, 2], 0), (0, 0, [3, 4], 0)]
    >>> partition_balance(even)
    1.0
    """
    live = [int((np.asarray(p[2]) > 0).sum()) for p in shard_pairs]
    total = sum(live)
    if total == 0 or not live:
        return 1.0
    return max(live) / (total / len(live))


# ---------------------------------------------------------------------------
# sparse-C two-phase pipeline: symbolic per-strip bound + CompactedC packers
# ---------------------------------------------------------------------------


def tile_col_occupancy(b: TiledCSR) -> np.ndarray:
    """(tile_cap, bn) bool — which lanes (output columns) of each B tile
    hold at least one nonzero. Row 0 (the reserved zero tile) is all
    False. This is the symbolic pass's B-side input: a C window's column
    support is the union of its touching tiles' occupied lanes.

    >>> b = tiled_csr_from_host(
    ...     HostCSR.from_dense(np.eye(8, dtype=np.float32)),
    ...     block_k=8, bn=8)
    >>> tile_col_occupancy(b).astype(int).tolist()
    [[0, 0, 0, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 1, 1]]
    """
    return np.asarray((np.asarray(b.tiles) != 0).any(axis=1))


def symbolic_strip_nnz(pairs, occupancy, *, nblocks: int, nnb: int
                       ) -> np.ndarray:
    """Symbolic phase: per-C-row-strip nnz upper bound from the live-pair
    stream — the tightening of ``core/spgemm.py``'s whole-matrix
    :func:`repro.core.spgemm.symbolic_nnz` scalar down to row-block
    granularity, without touching a single value.

    For strip ``blk``, ``ub[blk] = Σ_j |∪ occupied lanes of the B tiles
    the live pairs (blk, j, slot) contract|``: any nonzero ``C[r, c]`` of
    a row ``r`` in the strip needs a ``k`` with ``A[r, k] ≠ 0`` (so the
    k-tile is live in A's block ``blk``) and ``B[k, c] ≠ 0`` (so tile
    ``(kb, j)`` is live and lane ``c % bn`` occupied) — hence every
    row's column support lies inside the per-window unions, and
    ``ub[blk]`` bounds each row's nnz in the strip. Exact (per row) when
    rows within a block share their A pattern and the contracted B tiles
    have disjoint, cancellation-free column supports.

    Vectorized: one lexsort groups pairs by (blk, j) window, one
    ``np.logical_or.reduceat`` over :func:`repro.core.segment.boundary_mask`
    run starts takes each window's lane union, and a
    :func:`repro.core.segment.segmented_sum` folds windows into strips.

    Returns (nblocks,) int64.
    """
    blocks, js, slots, _ = (np.asarray(p) for p in pairs)
    occ = np.asarray(occupancy, dtype=bool)
    live = slots > 0
    b = blocks[live].astype(np.int64)
    j = js[live].astype(np.int64)
    s = slots[live].astype(np.int64)
    if b.size == 0:
        return np.zeros(nblocks, dtype=np.int64)
    key = b * nnb + j
    order = np.argsort(key, kind="stable")
    skey, ss = key[order], s[order]
    first = boundary_mask(skey)
    starts = np.flatnonzero(first)
    union = np.logical_or.reduceat(occ[ss], starts, axis=0)  # (W, bn)
    counts = union.sum(axis=1).astype(np.float64)
    return segmented_sum(skey[first] // nnb, counts,
                         nblocks).astype(np.int64)


def symbolic_strip_nnz_reference(pairs, occupancy, *, nblocks: int,
                                 nnb: int) -> np.ndarray:
    """Loop reference for :func:`symbolic_strip_nnz` (test oracle)."""
    blocks, js, slots, _ = (np.asarray(p) for p in pairs)
    occ = np.asarray(occupancy, dtype=bool)
    ub = np.zeros(nblocks, dtype=np.int64)
    for blk in range(nblocks):
        for j in range(nnb):
            union = np.zeros(occ.shape[1], dtype=bool)
            for t in range(blocks.shape[0]):
                if (int(blocks[t]) == blk and int(js[t]) == j
                        and int(slots[t]) > 0):
                    union |= occ[int(slots[t])]
            ub[blk] += int(union.sum())
    return ub


def compacted_c_table(pairs, *, nblocks: int, nnb: int
                      ) -> tuple[np.ndarray, int]:
    """Slab table of the live C windows: the distinct ``(blk, j)`` windows
    touched by a live pair get slabs ``1..nlive`` in ascending window-key
    order (:func:`repro.core.segment.key_table` with ``base=1`` — slab 0
    stays the reserved zero slab, the :class:`TiledCSR` convention).
    Windows no live pair touches are provably all-zero, so the numeric
    phase never writes them. Returns ``(table, nslabs_live)``.

    >>> table, n = compacted_c_table(([0, 1], [1, 0], [3, 5], [0, 1]),
    ...                              nblocks=2, nnb=2)
    >>> table.tolist(), n
    ([0, 1, 2, 0], 2)
    """
    blocks, js, slots, _ = (np.asarray(p) for p in pairs)
    live = slots > 0
    key = blocks[live].astype(np.int64) * nnb + js[live].astype(np.int64)
    ukey = np.unique(key)
    return key_table(ukey, nblocks * nnb, base=1), int(ukey.size)


def compacted_c_positions(table, rows, cols, *, block_r: int, bn: int,
                          nnb: int) -> np.ndarray:
    """Where C's entry ``(rows[i], cols[i])`` sits in the flattened
    :class:`CompactedC` slabs of ``table``; an entry of a dead window
    reads the reserved zero slab. int64.

    >>> table, _ = compacted_c_table(([0], [1], [3], [0]), nblocks=1,
    ...                              nnb=2)
    >>> compacted_c_positions(table, np.array([2, 2]), np.array([5, 1]),
    ...                       block_r=4, bn=4, nnb=2).tolist()
    [25, 0]
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    slab = np.asarray(table, dtype=np.int64)[rows // block_r * nnb
                                             + cols // bn]
    return np.where(slab > 0, (slab * block_r + rows % block_r) * bn
                    + cols % bn, 0)


def compacted_c_from_dense(dense, table, *, nrows: int, ncols: int,
                           block_r: int, bn: int) -> CompactedC:
    """XLA segment-compaction epilogue: gather the live ``(block_r, bn)``
    windows of a dense C into packed :class:`CompactedC` slabs. This is
    the off-TPU fallback of the sparse-C kernels' windowed-scatter
    epilogue — same table, same slab order, bit-identical slabs (values
    are moved, never recomputed)."""
    table = np.asarray(table, dtype=np.int32)
    nblocks = (nrows + block_r - 1) // block_r
    nnb = (ncols + bn - 1) // bn
    dense = jnp.asarray(dense)
    pad_r = nblocks * block_r - dense.shape[0]
    pad_c = nnb * bn - dense.shape[1]
    if pad_r or pad_c:
        dense = jnp.pad(dense, ((0, max(pad_r, 0)), (0, max(pad_c, 0))))
    # (nblocks, block_r, nnb, bn) → (window, block_r, bn), window-major
    windows = dense.reshape(nblocks, block_r, nnb, bn).transpose(0, 2, 1, 3)
    windows = windows.reshape(nblocks * nnb, block_r, bn)
    live_keys = np.flatnonzero(table > 0)
    slabs = jnp.concatenate(
        [jnp.zeros((1, block_r, bn), dense.dtype), windows[live_keys]],
        axis=0)
    return CompactedC(slabs=slabs, table=jnp.asarray(table),
                      nrows=nrows, ncols=ncols, block_r=block_r, bn=bn)


def compacted_c_to_host(c: CompactedC) -> HostCSR:
    """CompactedC → HostCSR, values moved bit-for-bit (the round-trip the
    sparse-C parity tests and the chain workload's per-hop repacking
    use). Windows are disjoint, so no duplicate summing happens."""
    table = np.asarray(c.table).reshape(c.nblocks, c.nnb)
    slabs = np.asarray(c.slabs)
    blk, j = np.nonzero(table > 0)
    if blk.size == 0:
        return HostCSR(np.zeros(c.nrows + 1, np.int64),
                       np.empty(0, np.int32), np.empty(0, np.float32),
                       (c.nrows, c.ncols))
    vals = slabs[table[blk, j]]                  # (L, block_r, bn)
    lw, rr, cc = np.nonzero(vals)
    rows = blk[lw] * c.block_r + rr
    cols = j[lw] * c.bn + cc
    data = vals[lw, rr, cc]
    keep = (rows < c.nrows) & (cols < c.ncols)
    return HostCSR.from_coo(rows[keep], cols[keep], data[keep],
                            (c.nrows, c.ncols), sum_duplicates=False)


def compacted_c_counters(c: CompactedC, *, c_nnz: int | None = None,
                         value_bytes: int = 4) -> dict:
    """C-side traffic counters of the sparse-C tier (units per
    :data:`COUNTER_UNITS`): what the dense row strips would have written
    to HBM vs what the compacted slabs actually write, plus the
    windowed-scatter epilogue's step count. ``c_nnz`` defaults to the
    numeric slab count (exact nnz(C) including cancellation); pass the
    structural count to match a boolean symbolic reference.

    >>> c = compacted_c_from_dense(
    ...     np.eye(8, dtype=np.float32), [1, 0],
    ...     nrows=8, ncols=16, block_r=8, bn=8)
    >>> k = compacted_c_counters(c)
    >>> k["c_nnz"], k["c_compaction_steps"]
    (8, 1)
    >>> k["c_bytes_dense"], k["c_bytes_sparse"]
    (512, 256)
    """
    live = c.nslabs_live
    if c_nnz is None:
        c_nnz = int(np.count_nonzero(np.asarray(c.slabs)))
    return {
        "c_nnz": int(c_nnz),
        "c_bytes_dense": c.nblocks * c.block_r * c.nnb * c.bn * value_bytes,
        "c_bytes_sparse": live * c.block_r * c.bn * value_bytes,
        "c_compaction_steps": live,
    }


# ---------------------------------------------------------------------------
# Analytic footprints (paper Fig. 11)
# ---------------------------------------------------------------------------


def csr_nbytes(h: HostCSR) -> int:
    """Plain-CSR footprint (8 B indptr, 4 B index, 4 B value — the
    paper's Fig. 11 baseline).

    >>> csr_nbytes(HostCSR.from_dense(np.eye(2, dtype=np.float32)))
    40
    """
    return h.nbytes()


def csr_cluster_nbytes_exact(h: HostCSR, boundaries: Sequence[int],
                             *, fixed_length: bool = False,
                             index_bytes: int = 4, value_bytes: int = 4,
                             ptr_bytes: int = 8) -> int:
    """Exact ragged CSR_Cluster footprint as the paper counts it.

    Per cluster: one col-id per *distinct* column + a value slab of
    (distinct_cols × cluster_size). Variable-length additionally stores the
    cluster-size array and a value-pointer array; fixed-length does not.

    Vectorized: distinct (cluster, column) pairs are counted from one
    ``np.unique`` over the joint key — no per-cluster merging. Identical
    byte counts to :func:`csr_cluster_nbytes_exact_reference`.
    """
    bounds = np.asarray(list(boundaries) + [h.nrows], dtype=np.int64)
    ncl = bounds.shape[0] - 1
    sizes = np.diff(bounds)
    rows = expand_indptr(h.indptr)
    cl = np.searchsorted(bounds, rows, side="right") - 1
    key = cl * max(h.ncols, 1) + h.indices.astype(np.int64)
    ucl = np.unique(key) // max(h.ncols, 1)
    distinct = segmented_count(ucl, ncl)
    total_cols = int(distinct.sum())
    total_vals = int((distinct * sizes).sum())
    n = (ncl + 1) * ptr_bytes + total_cols * index_bytes \
        + total_vals * value_bytes
    if not fixed_length:
        n += ncl * index_bytes          # cluster sizes
        n += (ncl + 1) * ptr_bytes      # value pointers
    return n


def csr_cluster_nbytes_exact_reference(h: HostCSR,
                                       boundaries: Sequence[int],
                                       *, fixed_length: bool = False,
                                       index_bytes: int = 4,
                                       value_bytes: int = 4,
                                       ptr_bytes: int = 8) -> int:
    """Loop reference for :func:`csr_cluster_nbytes_exact` (test oracle)."""
    bounds = list(boundaries) + [h.nrows]
    ncl = len(bounds) - 1
    total_cols = 0
    total_vals = 0
    for c in range(ncl):
        lo, hi = bounds[c], bounds[c + 1]
        merged = np.unique(np.concatenate(
            [h.row(i)[0] for i in range(lo, hi)] or [np.empty(0, np.int32)]))
        total_cols += merged.size
        total_vals += merged.size * (hi - lo)
    n = (ncl + 1) * ptr_bytes + total_cols * index_bytes \
        + total_vals * value_bytes
    if not fixed_length:
        n += ncl * index_bytes          # cluster sizes
        n += (ncl + 1) * ptr_bytes      # value pointers
    return n
