"""SpGEMM / SpMM compute: row-wise (Gustavson) and cluster-wise (Alg. 1) —
the XLA gather/scatter tier, and the fallback of the Pallas kernel tier.

All functions are shape-static and jittable. Outputs are dense accumulators
(M×N) — on TPU the sparse-hash accumulator of the CPU algorithm has no
efficient analogue, and for the paper's workloads (A², square×tall-skinny)
the comparison between row-wise and cluster-wise is unaffected: both variants
share the identical scatter-accumulate epilogue and differ exactly where the
paper's variants differ — in how rows of B are fetched and reused.

Dataflow correspondence (paper → here):

* row-wise Gustavson: one gather of a B row per *nonzero* of A
  (:func:`spgemm_rowwise_dense` / :func:`spmm_rowwise`).
* cluster-wise (Alg. 1): one gather of a B row per *(cluster, column)* slot —
  deduplicated across the rows of the cluster — then an outer product against
  the cluster's value slab (:func:`spgemm_clusterwise_dense` /
  :func:`spmm_clusterwise`). The gather-volume reduction is the TPU analogue
  of the paper's cache-reuse win.

Relation to the Pallas kernel tier (``repro.kernels.cluster_spgemm``): the
planner scores a ``pallas`` scheme — BCC(A) × TiledCSR(B) on the MXU —
alongside these XLA paths. The Pallas path wins when the (reordered)
pattern is block-dense enough that B's live-tile footprint
(:func:`b_bytes_tiled`) undercuts the gather path's per-nonzero re-fetch
volume (:func:`b_bytes_rowwise_binned`) — hub/community/RMAT structure;
the gather paths here remain both the interpret/CPU fallback and the
winner on patterns whose 128-lane tiles stay mostly dead (banded/ER). The
``b_bytes_*`` counters are the decision's measurable core and feed the
``kernels`` benchmark table.

``flops_*`` helpers report the multiply-add count each variant performs
(including padding waste for the clustered format) — used by the benchmark
harness and the §Roofline analysis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import CSR, CSRCluster, HostCSR

__all__ = [
    "spgemm_rowwise_dense", "spgemm_clusterwise_dense",
    "spgemm_rowwise_dense_binned", "spgemm_clusterwise_dense_binned",
    "length_bins", "slot_rows_host",
    "spmm_rowwise", "spmm_clusterwise",
    "spgemm_reference", "symbolic_nnz", "symbolic_row_nnz", "flops_spgemm",
    "gathers_rowwise", "gathers_clusterwise",
    "b_bytes_rowwise_binned", "b_bytes_tiled",
]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _slot_rows(indptr: jax.Array, cap: int) -> jax.Array:
    """Row id of each storage slot (padded slots map past the last row)."""
    return jnp.searchsorted(indptr,
                            jnp.arange(cap, dtype=indptr.dtype),
                            side="right").astype(jnp.int32) - 1


def slot_rows_host(indptr: np.ndarray, cap: int) -> np.ndarray:
    """Host-side :func:`_slot_rows`: row id of each of ``cap`` storage
    slots. Precomputed once per packed operand and threaded through the
    binned drivers so no per-bin pass re-derives it."""
    return (np.searchsorted(np.asarray(indptr),
                            np.arange(cap, dtype=np.int64),
                            side="right") - 1).astype(np.int32)


def _gather_b_row(b: CSR, k: jax.Array, max_row_b: int
                  ) -> tuple[jax.Array, jax.Array]:
    """Fixed-width gather of B row ``k``: (cols, vals), masked past row end.

    ``k`` may be the padding sentinel ``b.nrows`` — yields an empty row.
    """
    k = jnp.clip(k, 0, b.nrows)
    start = b.indptr[k]
    length = b.indptr[jnp.clip(k + 1, 0, b.nrows)] - start
    offs = jnp.arange(max_row_b, dtype=jnp.int32)
    idx = jnp.clip(start + offs, 0, b.nnz_cap - 1)
    mask = offs < length
    cols = jnp.where(mask, b.indices[idx], b.ncols)
    vals = jnp.where(mask, b.data[idx], 0.0)
    return cols, vals


# ---------------------------------------------------------------------------
# sparse × sparse (A², paper §4.2–4.3)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_row_b",))
def spgemm_rowwise_dense(a: CSR, b: CSR, max_row_b: int) -> jax.Array:
    """Gustavson row-wise SpGEMM; returns dense C (nrows_a × ncols_b)."""
    rows = _slot_rows(a.indptr, a.nnz_cap)               # (nnz_a,)
    ks = a.indices                                        # (nnz_a,)
    valid = ks < a.ncols
    bcols, bvals = jax.vmap(
        lambda k: _gather_b_row(b, k, max_row_b))(
        jnp.where(valid, ks, b.nrows))                    # (nnz_a, W)
    prod = a.data[:, None] * bvals                        # (nnz_a, W)
    out_rows = jnp.broadcast_to(
        jnp.clip(rows, 0, a.nrows - 1)[:, None], prod.shape)
    out_cols = jnp.minimum(bcols, b.ncols)
    c = jnp.zeros((a.nrows, b.ncols + 1), prod.dtype)
    c = c.at[out_rows, out_cols].add(prod)
    return c[:, : b.ncols]


@functools.partial(jax.jit, static_argnames=("max_row_b",))
def spgemm_clusterwise_dense(a: CSRCluster, b: CSR,
                             max_row_b: int) -> jax.Array:
    """Cluster-wise SpGEMM (Alg. 1); returns dense C.

    One B-row gather per (cluster, column) slot; the gathered row is applied
    to *all* rows of the cluster via an outer product with the value slab —
    the reuse the CSR_Cluster format exists to create.
    """
    slot_cluster = jnp.searchsorted(
        a.cluster_ptr, jnp.arange(a.slot_cap, dtype=jnp.int32),
        side="right").astype(jnp.int32) - 1               # (S,)
    cl = jnp.clip(slot_cluster, 0, a.nclusters - 1)
    ks = a.cols                                           # (S,)
    valid = ks < a.ncols
    bcols, bvals = jax.vmap(
        lambda k: _gather_b_row(b, k, max_row_b))(
        jnp.where(valid, ks, b.nrows))                    # (S, W)
    # outer product, laid out (S, W, K) so the K rows of a cluster form the
    # contiguous window of one scatter update: the epilogue then issues one
    # K-row windowed add per (slot, B-column) instead of K scalar adds —
    # same math, K× fewer scatter indices (the paper's CPU kernel likewise
    # pays per cluster member touched, not per padding element)
    prod = bvals[:, :, None] * a.values[:, None, :]       # (S, W, K)
    base = jnp.clip(a.row_base[cl], 0, a.nrows)           # (S,)
    idx_rows = jnp.broadcast_to(base[:, None], bcols.shape)
    idx_cols = jnp.minimum(bcols, b.ncols)
    indices = jnp.stack([idx_rows, idx_cols], axis=-1).reshape(-1, 2)
    updates = prod.reshape(-1, a.max_cluster)
    c = jnp.zeros((a.nrows + a.max_cluster, b.ncols + 1), prod.dtype)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,),
        inserted_window_dims=(1,),
        scatter_dims_to_operand_dims=(0, 1))
    c = jax.lax.scatter_add(c, indices, updates, dnums)
    return c[: a.nrows, : b.ncols]


# ---------------------------------------------------------------------------
# length-binned variants (Nagasaka-style row binning / propagation blocking)
#
# The single-pass kernels above pad every B-row gather to the *global* max
# row length W; on skewed inputs (hub columns) one 400-nnz row inflates W —
# and with it the scatter volume — 30–50×, while the p99 row is ~10 wide.
# The binned variants take a host-computed partition of the storage slots by
# pow2 bucket of their fetched B-row length and run one pass per bin, so
# each slot pays the gather/scatter width of *its* row, not the maximum.
# Slots fetching empty rows are dropped outright (they contribute nothing).
# Same math, same dataflow — only the padding waste goes away.
# ---------------------------------------------------------------------------


# most scatter updates (slots × width) one bin pass may issue. A pass
# materializes its (slots·width, cluster) update and (slots·width, 2)
# index arrays, which the TPU lays out with their minor dimension padded
# to 128 lanes: at this bound they take ~1 GiB of HBM, where one pass of
# a Graph500 scale-14 hub bucket (2^24 updates) asked for 17.6 GB.
MAX_PASS_UPDATES = 1 << 20


def length_bins(fetch_lens: np.ndarray, *, floor: int = 8,
                pad_sentinel: int | None = None
                ) -> list[tuple[np.ndarray, int]]:
    """Partition slot ids 0..len(fetch_lens)-1 by pow2 bucket of their
    fetched B-row length.

    Returns [(slot_ids, width)] with slot_ids padded to a pow2 length using
    ``pad_sentinel`` (default: len(fetch_lens), i.e. one past the last slot
    — the kernels mask slots >= their cap). Zero-length fetches appear in
    no bin. A bucket with more than :data:`MAX_PASS_UPDATES` slot × width
    updates is split, in slot order, into several bins of the same width.
    """
    fetch_lens = np.asarray(fetch_lens, dtype=np.int64)
    sentinel = (int(fetch_lens.shape[0]) if pad_sentinel is None
                else pad_sentinel)
    live = np.flatnonzero(fetch_lens > 0)
    if live.size == 0:
        return []
    widths = np.maximum(fetch_lens[live], 1)
    buckets = np.maximum(floor, 2 ** np.ceil(np.log2(widths)).astype(int))
    bins: list[tuple[np.ndarray, int]] = []
    for w in np.unique(buckets):
        slots = live[buckets == w]
        per = max(8, 1 << (max(MAX_PASS_UPDATES // int(w), 1)
                           .bit_length() - 1))
        for lo in range(0, slots.size, per):
            part = slots[lo:lo + per]
            cap = max(8, 1 << (int(part.size) - 1).bit_length())
            padded = np.full(cap, sentinel, dtype=np.int32)
            padded[: part.size] = part
            bins.append((padded, int(w)))
    return bins


@functools.partial(jax.jit, static_argnames=("max_row_b",), donate_argnums=3)
def _rowwise_pass(a: CSR, b: CSR, slots: jax.Array, c: jax.Array,
                  slot_rows: jax.Array, max_row_b: int) -> jax.Array:
    valid_slot = slots < a.nnz_cap
    sl = jnp.clip(slots, 0, a.nnz_cap - 1)
    rows = slot_rows[sl]
    ks = jnp.where(valid_slot, a.indices[sl], a.ncols)
    data = jnp.where(valid_slot, a.data[sl], 0.0)
    valid = ks < a.ncols
    bcols, bvals = jax.vmap(
        lambda k: _gather_b_row(b, k, max_row_b))(
        jnp.where(valid, ks, b.nrows))
    prod = data[:, None] * bvals
    out_rows = jnp.broadcast_to(
        jnp.clip(rows, 0, a.nrows - 1)[:, None], prod.shape)
    out_cols = jnp.minimum(bcols, b.ncols)
    return c.at[out_rows, out_cols].add(prod)


def spgemm_rowwise_dense_binned(a: CSR, b: CSR,
                                bins: list[tuple[np.ndarray, int]],
                                slot_rows: np.ndarray | None = None
                                ) -> jax.Array:
    """Row-wise SpGEMM with per-bin gather widths; equals
    :func:`spgemm_rowwise_dense` for any valid slot partition.

    ``slot_rows`` — optional precomputed slot→row map
    (:func:`slot_rows_host`); computed once here otherwise, and shared by
    every bin pass instead of being re-derived per bin.
    """
    if slot_rows is None:
        slot_rows = slot_rows_host(np.asarray(a.indptr), a.nnz_cap)
    sr = jnp.asarray(slot_rows)
    c = jnp.zeros((a.nrows, b.ncols + 1), a.data.dtype)
    for slots, w in bins:
        c = _rowwise_pass(a, b, jnp.asarray(slots), c, sr, w)
    return c[:, : b.ncols]


@functools.partial(jax.jit, static_argnames=("max_row_b",), donate_argnums=3)
def _clusterwise_pass(a: CSRCluster, b: CSR, slots: jax.Array, c: jax.Array,
                      slot_clusters: jax.Array, max_row_b: int) -> jax.Array:
    valid_slot = slots < a.slot_cap
    sl = jnp.clip(slots, 0, a.slot_cap - 1)
    cl = jnp.clip(slot_clusters[sl], 0, a.nclusters - 1)
    ks = jnp.where(valid_slot, a.cols[sl], a.ncols)
    slab = jnp.where(valid_slot[:, None], a.values[sl], 0.0)
    valid = ks < a.ncols
    bcols, bvals = jax.vmap(
        lambda k: _gather_b_row(b, k, max_row_b))(
        jnp.where(valid, ks, b.nrows))
    prod = bvals[:, :, None] * slab[:, None, :]           # (S, W, K)
    base = jnp.clip(a.row_base[cl], 0, a.nrows)
    idx_rows = jnp.broadcast_to(base[:, None], bcols.shape)
    idx_cols = jnp.minimum(bcols, b.ncols)
    indices = jnp.stack([idx_rows, idx_cols], axis=-1).reshape(-1, 2)
    updates = prod.reshape(-1, a.max_cluster)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,),
        inserted_window_dims=(1,),
        scatter_dims_to_operand_dims=(0, 1))
    return jax.lax.scatter_add(c, indices, updates, dnums)


def spgemm_clusterwise_dense_binned(a: CSRCluster, b: CSR,
                                    bins: list[tuple[np.ndarray, int]],
                                    slot_clusters: np.ndarray | None = None
                                    ) -> jax.Array:
    """Cluster-wise SpGEMM with per-bin gather widths; equals
    :func:`spgemm_clusterwise_dense` for any valid slot partition.

    ``slot_clusters`` — optional precomputed slot→cluster map
    (:func:`slot_rows_host` over ``cluster_ptr``); computed once here
    otherwise and shared across the bin passes.
    """
    if slot_clusters is None:
        slot_clusters = slot_rows_host(np.asarray(a.cluster_ptr), a.slot_cap)
    sc = jnp.asarray(slot_clusters)
    c = jnp.zeros((a.nrows + a.max_cluster, b.ncols + 1), a.values.dtype)
    for slots, w in bins:
        c = _clusterwise_pass(a, b, jnp.asarray(slots), c, sc, w)
    return c[: a.nrows, : b.ncols]


# ---------------------------------------------------------------------------
# sparse × dense tall-skinny (paper §4.4)
# ---------------------------------------------------------------------------


@jax.jit
def spmm_rowwise(a: CSR, bdense: jax.Array) -> jax.Array:
    """Row-wise CSR × dense: one gather of B[k, :] per nonzero of A."""
    rows = _slot_rows(a.indptr, a.nnz_cap)
    ks = a.indices
    valid = ks < a.ncols
    brows = bdense[jnp.where(valid, ks, 0)]               # (nnz_a, N)
    prod = jnp.where(valid, a.data, 0.0)[:, None] * brows
    c = jnp.zeros((a.nrows, bdense.shape[1]), prod.dtype)
    return c.at[jnp.clip(rows, 0, a.nrows - 1)].add(prod)


@jax.jit
def spmm_clusterwise(a: CSRCluster, bdense: jax.Array) -> jax.Array:
    """Cluster-wise CSR_Cluster × dense: one gather per (cluster, column)."""
    slot_cluster = jnp.searchsorted(
        a.cluster_ptr, jnp.arange(a.slot_cap, dtype=jnp.int32),
        side="right").astype(jnp.int32) - 1
    cl = jnp.clip(slot_cluster, 0, a.nclusters - 1)
    ks = a.cols
    valid = ks < a.ncols
    brows = bdense[jnp.where(valid, ks, 0)]               # (S, N)
    brows = jnp.where(valid[:, None], brows, 0.0)
    prod = a.values[:, :, None] * brows[:, None, :]       # (S, K, N)
    base = a.row_base[cl]
    kk = jnp.arange(a.max_cluster, dtype=jnp.int32)
    out_rows = jnp.clip(base[:, None] + kk[None, :], 0, a.nrows)  # (S, K)
    c = jnp.zeros((a.nrows + 1, bdense.shape[1]), prod.dtype)
    c = c.at[out_rows].add(prod)
    return c[: a.nrows]


# ---------------------------------------------------------------------------
# oracle + metrics
# ---------------------------------------------------------------------------


def spgemm_reference(a: HostCSR, b: HostCSR) -> np.ndarray:
    """Pure-numpy oracle: densify and matmul."""
    return a.to_dense() @ b.to_dense()


def symbolic_nnz(a: HostCSR, b: HostCSR) -> int:
    """Symbolic-phase nnz(C) (exact, host-side, whole-matrix scalar).

    The sparse-C tier tightens this to per-row-strip granularity from the
    live-pair stream — :func:`repro.core.formats.symbolic_strip_nnz` —
    without densifying either operand; this dense-boolean scalar stays as
    the exact oracle those bounds are property-tested against."""
    c = (a.to_dense() != 0).astype(np.float32) @ \
        (b.to_dense() != 0).astype(np.float32)
    return int((c != 0).sum())


def symbolic_row_nnz(a: HostCSR, b: HostCSR) -> np.ndarray:
    """Exact per-row nnz(C) (structural — cancellation ignored), the
    row-granular oracle for the sparse-C symbolic pass: for every row
    block, ``symbolic_strip_nnz``'s per-strip bound must dominate each of
    these rows."""
    c = (a.to_dense() != 0).astype(np.float32) @ \
        (b.to_dense() != 0).astype(np.float32)
    return (c != 0).sum(axis=1).astype(np.int64)


def flops_spgemm(a: HostCSR, b: HostCSR) -> int:
    """2 × Σ_{a_ik ≠ 0} nnz(B row k) — the standard SpGEMM flop count."""
    bn = b.row_nnz()
    return int(2 * bn[a.indices.astype(np.int64)].sum())


def gathers_rowwise(a: HostCSR) -> int:
    """Number of B-row fetches the row-wise dataflow performs."""
    return a.nnz


def gathers_clusterwise(nslots: int) -> int:
    """Number of B-row fetches the cluster-wise dataflow performs
    (= deduplicated (cluster, column) slots)."""
    return nslots


def b_bytes_rowwise_binned(bins: list[tuple[np.ndarray, int]],
                           nslots: int) -> int:
    """B bytes the binned XLA gather path moves per A² call: every live
    slot fetches its B row padded to the bin width — 8 B (int32 index +
    f32 value) per fetched element, re-fetched per A nonzero (the gather
    machinery provides no cross-row reuse)."""
    total = 0
    for slots, w in bins:
        total += int((np.asarray(slots) < nslots).sum()) * w * 8
    return total


def b_bytes_tiled(nlive_tiles: int, block_k: int = 128,
                  bn: int = 128) -> int:
    """B bytes the VMEM-resident Pallas tiled path moves per A² call: each
    live dense tile streams HBM→VMEM exactly once (4 B/slot, no indices)
    and is reused by every cluster slab that touches it."""
    return nlive_tiles * block_k * bn * 4
