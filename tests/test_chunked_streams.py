"""Pair streams launched in SMEM-sized chunks (interpret mode): every
chunked kernel is bit-identical to its one-launch stream, including
windows whose steps straddle a chunk boundary, a stream exactly one
chunk long and an empty stream."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import (HostCSR, bcc_from_host, revisit_pair_stream,
                                tiled_csr_from_host)
from repro.core.spgemm import spgemm_reference
from repro.kernels import ops
from repro.kernels.cluster_spgemm import (cluster_spgemm_pairs,
                                          cluster_spgemm_pairs_db,
                                          cluster_spgemm_pairs_resident,
                                          cluster_spgemm_pairs_sharded,
                                          cluster_spgemm_pairs_sparse,
                                          cluster_spgemm_pairs_sparse_db,
                                          cluster_spgemm_pairs_window)
from repro.kernels.cluster_spmm import cluster_spmm_compact
from repro.core.formats import partition_pair_stream

pytestmark = pytest.mark.pallas

KW = dict(block_r=8, block_k=16, bn=16)


def rand_host(n, m, density, seed):
    rng = np.random.default_rng(seed)
    dense = (rng.random((n, m)) < density) * rng.uniform(
        0.5, 2.0, (n, m)).astype(np.float32)
    return HostCSR.from_dense(dense.astype(np.float32))


@pytest.fixture(scope="module")
def packed():
    a = rand_host(72, 72, 0.1, 7)       # 9 row blocks of ragged length
    bcc = bcc_from_host(a, block_r=8, block_k=16)
    tiled = tiled_csr_from_host(a, block_k=16, bn=16)
    stream = ops.bcc_compact_stream(bcc, cover_all_blocks=True)
    pairs = ops.build_live_pairs(bcc, tiled, stream)
    return a, bcc, tiled, stream, pairs


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
    _, _, tiled, stream, pairs = packed
    assert _straddles(pairs[0], chunk)
    args = (*(jnp.asarray(p) for p in pairs), jnp.asarray(stream[2]),
            tiled.tiles)
    kw = dict(KW, nblocks=9, nnb=tiled.nnb, interpret=True)
    whole = np.asarray(kernel(*args, **kw))
    got = np.asarray(kernel(*args, chunk=chunk, **kw))
    np.testing.assert_array_equal(got, whole)


@pytest.mark.parametrize("short", [0, 8])
def test_stream_one_chunk_long(packed, short):
    """A stream exactly one chunk long (one launch), and one a step
    group longer than its chunk (two launches, the second mostly tail
    padding)."""
    _, _, tiled, stream, pairs = packed
    t = pairs[0].shape[0]
    assert t % 8 == 0
    args = (*(jnp.asarray(p) for p in pairs), jnp.asarray(stream[2]),
            tiled.tiles)
    kw = dict(KW, nblocks=9, nnb=tiled.nnb, interpret=True)
    whole = np.asarray(cluster_spgemm_pairs_db(*args, **kw))
    got = np.asarray(cluster_spgemm_pairs_db(*args, chunk=t - short, **kw))
    np.testing.assert_array_equal(got, whole)


def test_empty_stream_returns_zero_c(packed):
    _, _, tiled, stream, _ = packed
    empty = jnp.zeros((0,), jnp.int32)
    got = np.asarray(cluster_spgemm_pairs(
        empty, empty, empty, empty, jnp.asarray(stream[2]), tiled.tiles,
        nblocks=9, nnb=tiled.nnb, chunk=8, interpret=True, **KW))
    assert got.shape == (72, tiled.nnb * 16) and not got.any()


def test_all_zero_operand_through_ops(monkeypatch):
    """An operand with no nonzero: a stream of zero-slot sentinels only,
    chunked, reads back an all-zero C."""
    monkeypatch.setattr(ops, "_SMEM_STREAM_BUDGET", 4 * 4 * 8)
    a = HostCSR.from_dense(np.zeros((40, 40), np.float32))
    bcc = bcc_from_host(a, block_r=8, block_k=16)
    tiled = tiled_csr_from_host(a, block_k=16, bn=16)
    got = np.asarray(ops.bcc_spgemm_tiled(bcc, tiled, interpret=True,
                                          sparse_c=False))
    assert got.shape == (40, 40) and not got.any()


def test_window_kernel_chunked_bitwise(packed):
    _, _, tiled, stream, pairs = packed
    wb = 2
    rv = revisit_pair_stream(pairs, window_blocks=wb)
    wins = (np.asarray(rv[0]) // wb).astype(np.int32)
    assert _straddles(wins, 16)
    args = (jnp.asarray(wins), *(jnp.asarray(p) for p in rv),
            jnp.asarray(stream[2]), tiled.tiles)
    kw = dict(KW, nblocks=9, nnb=tiled.nnb, window_blocks=wb,
              interpret=True)
    whole = np.asarray(cluster_spgemm_pairs_window(*args, **kw))
    got = np.asarray(cluster_spgemm_pairs_window(*args, chunk=16, **kw))
    np.testing.assert_array_equal(got, whole)


@pytest.mark.parametrize("kernel", [cluster_spgemm_pairs_sparse,
                                    cluster_spgemm_pairs_sparse_db])
def test_sparse_c_window_straddling_chunks_bitwise(packed, kernel):
    _, bcc, tiled, stream, pairs = packed
    c_slots, slots, a_idx, _, nslabs = ops.build_sparse_c_pairs(
        bcc, tiled, pairs, stream)
    assert _straddles(c_slots, 8)
    args = (jnp.asarray(c_slots), jnp.asarray(slots), jnp.asarray(a_idx),
            jnp.asarray(stream[2]), tiled.tiles)
    kw = dict(KW, nslabs=int(nslabs), interpret=True)
    whole = np.asarray(kernel(*args, **kw))
    got = np.asarray(kernel(*args, chunk=8, **kw))
    np.testing.assert_array_equal(got, whole)


def test_spmm_compact_chunked_bitwise(packed):
    _, bcc, _, _, _ = packed
    block_ids, tile_ids, values = ops.bcc_compact_stream(
        bcc, cover_all_blocks=True)
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
    _, _, tiled, stream, pairs = packed
    ranges, sp = partition_pair_stream(pairs, nblocks=9, num_shards=3)
    kw = dict(KW, nblocks=9, nnb=tiled.nnb, interpret=True)
    values = jnp.asarray(stream[2])
    whole = np.asarray(cluster_spgemm_pairs_sharded(
        sp, ranges, values, tiled.tiles, **kw))
    got = np.asarray(cluster_spgemm_pairs_sharded(
        sp, ranges, values, tiled.tiles, chunk=8, **kw))
    np.testing.assert_array_equal(got, whole)


@pytest.mark.parametrize("sparse_c", [False, True])
def test_ops_chunks_at_the_smem_budget(monkeypatch, packed, sparse_c):
    """The ops wrappers size chunks from the SMEM budget: a budget of a
    few dozen steps gives the same product as the reference."""
    a, bcc, tiled, _, _ = packed
    monkeypatch.setattr(ops, "_SMEM_STREAM_BUDGET", 4 * 5 * 16)
    assert ops.stream_chunk(4) == 16
    got = np.asarray(ops.bcc_spgemm_tiled(bcc, tiled, interpret=True,
                                          sparse_c=sparse_c))
    np.testing.assert_allclose(got, spgemm_reference(a, a), rtol=1e-5,
                               atol=1e-5)


def test_padded_grid_over_smem_budget_raises(monkeypatch, packed):
    _, bcc, tiled, _, _ = packed
    monkeypatch.setattr(ops, "_SMEM_STREAM_BUDGET", 64)
    with pytest.raises(ValueError, match="SMEM"):
        ops.bcc_spgemm_tiled(bcc, tiled, compact=False, interpret=True)
