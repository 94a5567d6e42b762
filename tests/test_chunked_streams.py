"""Pair streams launched in SMEM-sized chunks (interpret mode): every
chunked kernel is bit-identical to its one-launch stream, including
windows whose steps straddle a chunk boundary, a stream exactly one
chunk long, an empty stream and row blocks 16 and 32 high. The packs
keep row blocks 8 high unless a test says otherwise."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import HostCSR, bcc_from_host
from repro.core.spgemm import spgemm_reference
from repro.kernels import ops
from repro.kernels.cluster_spgemm import (cluster_spgemm_pairs,
                                          cluster_spgemm_pairs_db,
                                          cluster_spgemm_pairs_resident,
                                          cluster_spgemm_pairs_sharded,
                                          cluster_spgemm_pairs_sparse,
                                          cluster_spgemm_pairs_sparse_db)
from repro.kernels.cluster_spmm import cluster_spmm_compact
from repro.core.formats import partition_pair_stream

from _packing import grouped, pack, product, scipy_product

pytestmark = pytest.mark.pallas

KW = dict(block_r=8, block_k=16, bn=16)


def rand_host(n, m, density, seed):
    rng = np.random.default_rng(seed)
    dense = (rng.random((n, m)) < density) * rng.uniform(
        0.5, 2.0, (n, m)).astype(np.float32)
    return HostCSR.from_dense(dense.astype(np.float32))


@pytest.fixture(scope="module")
def packed():
    """A², its sparse-C pattern, the pattern's value set, live pairs and
    window-major sparse-C stream."""
    a = rand_host(72, 72, 0.1, 7)       # 9 row blocks of ragged length
    pattern = pack(a, a, block_k=16, bn=16, sparse_out=True)
    values, tiled = pattern.fill(a.data)
    return a, values, tiled, pattern.pairs, pattern.sparse_pairs


def _straddles(key, chunk) -> bool:
    """Whether some window's steps run across a chunk boundary."""
    key = np.asarray(key)
    n = -(-key.size // chunk)
    length = (-(-key.size // n) + 7) // 8 * 8
    cuts = np.arange(length, key.size, length)
    return bool(np.any(key[cuts - 1] == key[cuts]))


@pytest.mark.parametrize("kernel", [cluster_spgemm_pairs,
                                    cluster_spgemm_pairs_db,
                                    cluster_spgemm_pairs_resident])
@pytest.mark.parametrize("chunk", [8, 24, 40])
def test_dense_pairs_chunked_bitwise(packed, kernel, chunk):
    _, values, tiled, pairs, _ = packed
    assert _straddles(pairs[0], chunk)
    args = (*pairs, values, tiled.tiles)
    kw = dict(KW, nblocks=9, nnb=tiled.nnb, interpret=True)
    whole = np.asarray(kernel(*args, **kw))
    got = np.asarray(kernel(*args, chunk=chunk, **kw))
    np.testing.assert_array_equal(got, whole)


@pytest.mark.parametrize("g", [16, 32])
@pytest.mark.parametrize("kernel", [cluster_spgemm_pairs,
                                    cluster_spgemm_pairs_db])
def test_dense_pairs_chunked_at_a_selected_height(kernel, g):
    """A pattern whose rows come in groups of ``g`` packs at row blocks
    ``g`` high (``ops.select_block_r``): its dense-strip stream, chunked
    so a strip straddles a boundary, is bit-identical to one launch and
    matches float64 scipy."""
    a, b = grouped(256, g, 16, g), rand_host(256, 48, 0.3, g + 1)
    pattern = pack(a, b, block_k=16, bn=16, block_r=None,
                   _SPARSE_C_DENSITY=-1.0)
    assert pattern.a.block_r == g
    values, tiled = pattern.fill(a.data, b.data)
    pairs = pattern.pairs
    assert _straddles(pairs[0], 16)
    args = (*pairs, values, tiled.tiles)
    kw = dict(block_r=g, block_k=16, bn=16, nblocks=256 // g,
              nnb=tiled.nnb, interpret=True)
    whole = np.asarray(kernel(*args, **kw))
    got = np.asarray(kernel(*args, chunk=16, **kw))
    np.testing.assert_array_equal(got, whole)
    np.testing.assert_allclose(got, scipy_product(a, b), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("short", [0, 8])
def test_stream_one_chunk_long(packed, short):
    """A stream exactly one chunk long (one launch), and one a step
    group longer than its chunk (two launches, the second mostly tail
    padding)."""
    _, values, tiled, pairs, _ = packed
    t = pairs[0].shape[0]
    assert t % 8 == 0
    args = (*pairs, values, tiled.tiles)
    kw = dict(KW, nblocks=9, nnb=tiled.nnb, interpret=True)
    whole = np.asarray(cluster_spgemm_pairs_db(*args, **kw))
    got = np.asarray(cluster_spgemm_pairs_db(*args, chunk=t - short, **kw))
    np.testing.assert_array_equal(got, whole)


def test_empty_stream_returns_zero_c(packed):
    _, values, tiled, _, _ = packed
    empty = jnp.zeros((0,), jnp.int32)
    got = np.asarray(cluster_spgemm_pairs(
        empty, empty, empty, empty, values, tiled.tiles,
        nblocks=9, nnb=tiled.nnb, chunk=8, interpret=True, **KW))
    assert got.shape == (72, tiled.nnb * 16) and not got.any()


def test_all_zero_operand_through_ops(monkeypatch):
    """An operand with no nonzero: a stream of zero-slot sentinels only,
    chunked, reads back an all-zero C."""
    monkeypatch.setattr(ops, "_SMEM_STREAM_BUDGET", 4 * 4 * 8)
    a = HostCSR.from_dense(np.zeros((40, 40), np.float32))
    pattern = pack(a, a, block_k=16, bn=16, _SPARSE_C_DENSITY=-1.0)
    assert pattern.route == "resident"
    got = product(pattern, a, a)
    assert got.shape == (40, 40) and not got.any()


@pytest.mark.parametrize("kernel", [cluster_spgemm_pairs_sparse,
                                    cluster_spgemm_pairs_sparse_db])
def test_sparse_c_window_straddling_chunks_bitwise(packed, kernel):
    _, values, tiled, _, sparse_pairs = packed
    c_slots, slots, a_idx, _, nslabs = sparse_pairs
    assert _straddles(c_slots, 8)
    args = (c_slots, slots, a_idx, values, tiled.tiles)
    kw = dict(KW, nslabs=int(nslabs), interpret=True)
    whole = np.asarray(kernel(*args, **kw))
    got = np.asarray(kernel(*args, chunk=8, **kw))
    np.testing.assert_array_equal(got, whole)


def test_spmm_compact_chunked_bitwise(packed):
    a = packed[0]
    block_ids, tile_ids, values = ops.bcc_compact_stream(
        bcc_from_host(a, block_r=8, block_k=16), cover_all_blocks=True)
    assert _straddles(block_ids, 8)
    b = jnp.asarray(np.random.default_rng(3).standard_normal((80, 32)),
                    jnp.float32)
    args = (jnp.asarray(block_ids), jnp.asarray(tile_ids),
            jnp.asarray(values), b)
    kw = dict(block_r=8, block_k=16, nblocks=9, bn=16, interpret=True)
    whole = np.asarray(cluster_spmm_compact(*args, **kw))
    # 12: the stream is not a multiple of the chunk, so the last chunk
    # carries masked tail steps
    for chunk in (8, 12):
        got = np.asarray(cluster_spmm_compact(*args, chunk=chunk, **kw))
        np.testing.assert_array_equal(got, whole)


def test_sharded_chunked_bitwise(packed):
    _, values, tiled, pairs, _ = packed
    ranges, sp = partition_pair_stream(pairs, nblocks=9, num_shards=3)
    kw = dict(KW, nblocks=9, nnb=tiled.nnb, interpret=True)
    whole = np.asarray(cluster_spgemm_pairs_sharded(
        sp, ranges, values, tiled.tiles, **kw))
    got = np.asarray(cluster_spgemm_pairs_sharded(
        sp, ranges, values, tiled.tiles, chunk=8, **kw))
    np.testing.assert_array_equal(got, whole)


@pytest.mark.parametrize("sparse_c", [False, True])
def test_ops_chunks_at_the_smem_budget(monkeypatch, packed, sparse_c):
    """The launchers size chunks from the SMEM budget: a budget of a
    few dozen steps gives the same product as the reference."""
    a = packed[0]
    monkeypatch.setattr(ops, "_SMEM_STREAM_BUDGET", 4 * 5 * 16)
    assert ops.stream_chunk(4) == 16
    pattern = pack(a, a, block_k=16, bn=16,
                   _SPARSE_C_DENSITY=1.0 if sparse_c else -1.0)
    assert pattern.route == ("sparse_c" if sparse_c else "resident")
    got = product(pattern, a, a)
    np.testing.assert_allclose(got, spgemm_reference(a, a), rtol=1e-5,
                               atol=1e-5)


def test_padded_grid_over_smem_budget_raises(monkeypatch, packed):
    a = packed[0]
    monkeypatch.setattr(ops, "_SMEM_STREAM_BUDGET", 64)
    with pytest.raises(ValueError, match="SMEM"):
        pack(a, a, block_k=16, bn=16, _COMPACT_C_STRIP_BUDGET=0)
