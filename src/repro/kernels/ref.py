"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["cluster_spmm_ref", "cluster_spmm_compact_ref",
           "cluster_spgemm_tiled_ref", "cluster_spgemm_pairs_ref",
           "cluster_spgemm_pairs_sharded_ref", "flash_attention_ref",
           "masked_spgemm_ref"]


def cluster_spmm_ref(tile_ids, a_values, b, *, block_r, block_k,
                     tiles_per_block):
    """Oracle for kernels.cluster_spmm: reassemble dense A, then matmul."""
    tile_ids = np.asarray(tile_ids)
    a_values = np.asarray(a_values)
    b = np.asarray(b)
    nslabs = a_values.shape[0]
    nblocks = nslabs // tiles_per_block
    k, n = b.shape
    a_dense = np.zeros((nblocks * block_r, k), dtype=a_values.dtype)
    for blk in range(nblocks):
        for t in range(tiles_per_block):
            s = blk * tiles_per_block + t
            c0 = int(tile_ids[s]) * block_k
            a_dense[blk * block_r:(blk + 1) * block_r, c0:c0 + block_k] \
                += a_values[s]
    return a_dense @ b


def cluster_spmm_compact_ref(block_ids, tile_ids, a_values, b, *,
                             block_r, block_k, nblocks):
    block_ids = np.asarray(block_ids)
    tile_ids = np.asarray(tile_ids)
    a_values = np.asarray(a_values)
    b = np.asarray(b)
    k, n = b.shape
    a_dense = np.zeros((nblocks * block_r, k), dtype=a_values.dtype)
    for s in range(a_values.shape[0]):
        blk = int(block_ids[s])
        c0 = int(tile_ids[s]) * block_k
        a_dense[blk * block_r:(blk + 1) * block_r, c0:c0 + block_k] \
            += a_values[s]
    return a_dense @ b


def cluster_spgemm_tiled_ref(block_ids, tile_ids, table, a_values, b_tiles,
                             *, block_r, block_k, bn, nblocks, nnb):
    """Oracle for kernels.cluster_spgemm: reassemble dense A and dense B
    from their packed forms, then matmul."""
    block_ids = np.asarray(block_ids)
    tile_ids = np.asarray(tile_ids)
    table = np.asarray(table)
    a_values = np.asarray(a_values)
    b_tiles = np.asarray(b_tiles)
    nkb = table.shape[0] // nnb
    a_dense = np.zeros((nblocks * block_r, nkb * block_k),
                       dtype=a_values.dtype)
    for s in range(a_values.shape[0]):
        r0 = int(block_ids[s]) * block_r
        c0 = int(tile_ids[s]) * block_k
        a_dense[r0:r0 + block_r, c0:c0 + block_k] += a_values[s]
    b_dense = np.zeros((nkb * block_k, nnb * bn), dtype=b_tiles.dtype)
    for kb in range(nkb):
        for nb in range(nnb):
            slot = int(table[kb * nnb + nb])
            b_dense[kb * block_k:(kb + 1) * block_k,
                    nb * bn:(nb + 1) * bn] = b_tiles[slot]
    return a_dense @ b_dense


def cluster_spgemm_pairs_ref(blocks, js, slots, a_idx, a_values, b_tiles,
                             *, block_r, block_k, bn, nblocks, nnb):
    """Oracle for the live-pair compacted kernels: walk the pair stream,
    contracting each live slot into its (block, j) strip of a zero C."""
    blocks = np.asarray(blocks)
    js = np.asarray(js)
    slots = np.asarray(slots)
    a_idx = np.asarray(a_idx)
    a_values = np.asarray(a_values, dtype=np.float32)
    b_tiles = np.asarray(b_tiles, dtype=np.float32)
    c = np.zeros((nblocks * block_r, nnb * bn), dtype=np.float32)
    for t in range(blocks.shape[0]):
        if slots[t] <= 0:
            continue                       # sentinel / tail pad: no MXU
        r0 = int(blocks[t]) * block_r
        c0 = int(js[t]) * bn
        c[r0:r0 + block_r, c0:c0 + bn] += (
            a_values[int(a_idx[t])] @ b_tiles[int(slots[t])])
    return c


def cluster_spgemm_pairs_sharded_ref(shard_pairs, block_ranges, a_values,
                                     b_tiles, *, block_r, block_k, bn,
                                     nblocks, nnb):
    """Oracle for the sharded pair kernel: walk every shard's sub-stream
    into the global C, checking that each pair lies in its shard's block
    range (strips are disjoint, so the shards' order is irrelevant)."""
    a_values = np.asarray(a_values, dtype=np.float32)
    b_tiles = np.asarray(b_tiles, dtype=np.float32)
    c = np.zeros((nblocks * block_r, nnb * bn), dtype=np.float32)
    for (start, end), (blocks, js, slots, a_idx) in zip(
            np.asarray(block_ranges), shard_pairs):
        for t in range(np.asarray(blocks).shape[0]):
            if slots[t] <= 0:
                continue
            blk = int(blocks[t])
            assert start <= blk < end, "pair outside its shard's range"
            r0 = blk * block_r
            c0 = int(js[t]) * bn
            c[r0:r0 + block_r, c0:c0 + bn] += (
                a_values[int(a_idx[t])] @ b_tiles[int(slots[t])])
    return c


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        scale: float | None = None):
    """Oracle attention: (B, H, Sq, D) x (B, H, Sk, D) -> (B, H, Sq, D)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[-2], k.shape[-2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def masked_spgemm_ref(a, b, mask) -> np.ndarray:
    """Oracle for the masked product ``(a · b) ∘ mask`` (``b=None``:
    ``a · a``) of :class:`repro.core.formats.HostCSR` operands: dense
    float32 ``jax.numpy`` at the highest matmul precision, no kernel, no
    cache. Returns the dense product, zero off the mask's pattern."""
    b = a if b is None else b
    with jax.default_matmul_precision("highest"):
        c = jnp.asarray(a.to_dense()) @ jnp.asarray(b.to_dense())
        return np.asarray(c * jnp.asarray(mask.to_dense()))
