"""Compile the Pallas stream kernels for a described TPU v5e (no chip
attached): the chip's compiler refuses what interpret mode cannot see —
SMEM and VMEM over-use, unaligned slices. Each kernel compiles at its
SMEM chunk length, over two chunks so the chunk loop is in the program,
at the widths of a Graph500 scale-14 operand (n = 16384: 2048 row
blocks, 128 column tiles) and every ``block_k`` of ``select_block_k``'s
menu, with fp32 and bf16 B, and the Sp×Sp kernels again at row blocks
taller than 8.

The topology is described inside a module fixture, never at import: only
the test worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.formats import partition_pair_stream
from repro.kernels import ops
from repro.kernels.cluster_spgemm import (cluster_spgemm_pairs,
                                          cluster_spgemm_pairs_db,
                                          cluster_spgemm_pairs_resident,
                                          cluster_spgemm_pairs_sharded,
                                          cluster_spgemm_pairs_sparse_db,
                                          cluster_spgemm_resident,
                                          cluster_spgemm_tiled)
from repro.kernels.cluster_spmm import cluster_spmm_compact

pytestmark = pytest.mark.pallas

BLOCK_R, BN, NNB, NBLOCKS = 8, 128, 128, 2048
N_SLABS = 4096                      # A slabs in the compact stream
TILE_CAP = 512                      # streamed B tiles


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _streams(n_streams, chunk_streams, sharding):
    """Shapes of ``n_streams`` int32 streams two chunks long."""
    t = 2 * ops.stream_chunk(chunk_streams)
    return [_sds((t,), jnp.int32, sharding)] * n_streams


BK = pytest.mark.parametrize("block_k", [128, 256, 512])
DT = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])


@BK
@DT
@pytest.mark.parametrize("kernel", [cluster_spgemm_pairs,
                                    cluster_spgemm_pairs_db,
                                    cluster_spgemm_pairs_resident])
def test_dense_pair_kernels_compile(one_chip, kernel, block_k, dtype):
    if kernel is cluster_spgemm_pairs_resident:   # B at the VMEM budget
        cap = ops._RESIDENT_B_BUDGET // (block_k * BN
                                         * jnp.dtype(dtype).itemsize)
    else:
        cap = TILE_CAP
    a = _sds((N_SLABS, BLOCK_R, block_k), jnp.float32, one_chip)
    b = _sds((cap, block_k, BN), dtype, one_chip)

    def fn(blocks, js, slots, a_idx, a, b):
        return kernel(blocks, js, slots, a_idx, a, b, block_r=BLOCK_R,
                      block_k=block_k, bn=BN, nblocks=NBLOCKS, nnb=NNB,
                      chunk=ops.stream_chunk(4))
    _compile(fn, *_streams(4, 4, one_chip), a, b)


# the padded grid at a B too wide for the compacted grid's C row strip:
# 1,024 column tiles (131,072 columns), 64 k-blocks and a stream of
# 16,384 steps, so the stream and B's table fill 384 KiB of the SMEM
# budget
PAD_NNB, PAD_NKB, PAD_S = 1024, 64, 16384


@BK
@DT
@pytest.mark.parametrize("kernel", [cluster_spgemm_tiled,
                                    cluster_spgemm_resident])
def test_padded_grid_compiles(one_chip, kernel, block_k, dtype):
    assert not ops.compact_grid_ok_ncols(PAD_NNB * BN)
    assert 4 * (2 * PAD_S + PAD_NKB * PAD_NNB) <= ops._SMEM_STREAM_BUDGET
    if kernel is cluster_spgemm_resident:         # B at the VMEM budget
        cap = ops._RESIDENT_B_BUDGET // (block_k * BN
                                         * jnp.dtype(dtype).itemsize)
    else:
        cap = TILE_CAP
    ids = _sds((PAD_S,), jnp.int32, one_chip)
    table = _sds((PAD_NKB * PAD_NNB,), jnp.int32, one_chip)
    a = _sds((PAD_S, BLOCK_R, block_k), jnp.float32, one_chip)
    b = _sds((cap, block_k, BN), dtype, one_chip)

    def fn(block_ids, tile_ids, table, a, b):
        return kernel(block_ids, tile_ids, table, a, b, block_r=BLOCK_R,
                      block_k=block_k, bn=BN, nblocks=NBLOCKS, nnb=PAD_NNB)
    _compile(fn, ids, ids, table, a, b)


@BK
@DT
def test_sparse_c_kernel_compiles(one_chip, block_k, dtype):
    a = _sds((N_SLABS, BLOCK_R, block_k), jnp.float32, one_chip)
    b = _sds((TILE_CAP, block_k, BN), dtype, one_chip)

    def fn(c_slots, slots, a_idx, a, b):
        return cluster_spgemm_pairs_sparse_db(
            c_slots, slots, a_idx, a, b, block_r=BLOCK_R, block_k=block_k,
            bn=BN, nslabs=NBLOCKS * NNB, chunk=ops.stream_chunk(3))
    _compile(fn, *_streams(3, 3, one_chip), a, b)


# row blocks taller than 8 (``ops.select_block_r``): the dense-strip
# kernels at a C row strip of the whole strip budget, 4,096 columns at
# height 128 as in the revalue graph's A·A, and the sparse-C kernel at
# the heights the R·A·P hops can take
@pytest.mark.parametrize("kernel, block_r, block_k", [
    (cluster_spgemm_pairs_db, 128, 512),
    (cluster_spgemm_pairs_db, 64, 512),
    (cluster_spgemm_pairs_resident, 128, 512)])
def test_dense_pair_kernels_compile_tall_blocks(one_chip, kernel, block_r,
                                                block_k):
    nnb = ops._COMPACT_C_STRIP_BUDGET // (block_r * BN * 4)
    assert ops.compact_grid_ok_ncols(nnb * BN, block_r=block_r)
    if kernel is cluster_spgemm_pairs_resident:   # B at the VMEM budget
        cap = ops._RESIDENT_B_BUDGET // (block_k * BN * 4)
    else:
        cap = TILE_CAP
    a = _sds((256, block_r, block_k), jnp.float32, one_chip)
    b = _sds((cap, block_k, BN), jnp.float32, one_chip)

    def fn(blocks, js, slots, a_idx, a, b):
        return kernel(blocks, js, slots, a_idx, a, b, block_r=block_r,
                      block_k=block_k, bn=BN, nblocks=32, nnb=nnb,
                      chunk=ops.stream_chunk(4))
    _compile(fn, *_streams(4, 4, one_chip), a, b)


@pytest.mark.parametrize("block_r", [32, 64, 128])
def test_sparse_c_kernel_compiles_tall_windows(one_chip, block_r):
    a = _sds((N_SLABS, block_r, 128), jnp.float32, one_chip)
    b = _sds((TILE_CAP, 128, BN), jnp.float32, one_chip)

    def fn(c_slots, slots, a_idx, a, b):
        return cluster_spgemm_pairs_sparse_db(
            c_slots, slots, a_idx, a, b, block_r=block_r, block_k=128,
            bn=BN, nslabs=N_SLABS, chunk=ops.stream_chunk(3))
    _compile(fn, *_streams(3, 3, one_chip), a, b)


@BK
@DT
def test_spmm_compact_compiles(one_chip, block_k, dtype):
    t = 2 * ops.stream_chunk(2)
    a = _sds((t, BLOCK_R, block_k), jnp.float32, one_chip)
    b = _sds((NNB * block_k, BN), dtype, one_chip)

    def fn(block_ids, tile_ids, a, b):
        return cluster_spmm_compact(block_ids, tile_ids, a, b,
                                    block_r=BLOCK_R, block_k=block_k,
                                    nblocks=NBLOCKS, bn=BN,
                                    chunk=ops.stream_chunk(2))
    _compile(fn, *_streams(2, 2, one_chip), a, b)


def test_sharded_kernel_compiles_on_four_chips(topo, monkeypatch):
    """The default multi-chip path: the pair stream partitioned over a
    4-device mesh, one chunked kernel per chip."""
    from repro.distributed import sharding
    mesh = Mesh(np.asarray(topo.devices[:4]), ("cores",))
    monkeypatch.setattr(sharding, "core_mesh", lambda n: mesh)
    rng = np.random.default_rng(0)
    t = 4 * ops.stream_chunk(4) + 24
    pairs = (np.sort(rng.integers(0, NBLOCKS, t)).astype(np.int32),
             rng.integers(0, NNB, t).astype(np.int32),
             rng.integers(0, TILE_CAP, t).astype(np.int32),
             rng.integers(0, N_SLABS, t).astype(np.int32))
    ranges, shard_pairs = partition_pair_stream(pairs, nblocks=NBLOCKS,
                                                num_shards=4)
    rep = NamedSharding(mesh, P())
    a = _sds((N_SLABS, BLOCK_R, 128), jnp.float32, rep)
    b = _sds((TILE_CAP, 128, BN), jnp.float32, rep)

    def fn(a, b):
        return cluster_spgemm_pairs_sharded(
            shard_pairs, ranges, a, b, block_r=BLOCK_R, block_k=128, bn=BN,
            nblocks=NBLOCKS, nnb=NNB, kernel=cluster_spgemm_pairs_db,
            chunk=ops.stream_chunk(4), use_shard_map=True)
    compiled = _compile(fn, a, b)
    assert "tpu_custom_call" in compiled.as_text()
