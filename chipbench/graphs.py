"""Graph500 Kronecker (R-MAT) generator, numpy only.

The edge sampler is a copy of ``repro.core.suite.gen_kron``'s: each of
``edgefactor * 2**scale`` edges picks its row and column bit by bit with
the initiator probabilities (A, B, C, D = 1 - A - B - C). Then, as the
Graph500 specification requires, the vertex labels are permuted at
random (from the same generator), so that the hubs do not sit at the
low ids. The pattern is symmetrized with self loops and duplicates
merged. Values are not drawn here: a deployment draws them from the
run's seed.
"""
from __future__ import annotations

import numpy as np


def kronecker_pattern(scale: int, edgefactor: int, graph_seed: int,
                      a: float = 0.57, b: float = 0.19, c: float = 0.19
                      ) -> tuple[np.ndarray, np.ndarray]:
    """CSR ``(indptr int64, indices int32)`` of the symmetrized graph
    with every self loop, column indices sorted within each row."""
    rng = np.random.default_rng(graph_seed)
    n = 1 << scale
    m = n * edgefactor
    rows = np.zeros(m, dtype=np.int64)
    cols = np.zeros(m, dtype=np.int64)
    for lvl in range(scale):
        bit_r = (rng.random(m) > a + b).astype(np.int64)
        thr = np.where(bit_r == 0, b / (a + b),
                       (1 - a - b - c) / max(1 - a - b, 1e-9))
        bit_c = (rng.random(m) < thr).astype(np.int64)
        rows |= bit_r << lvl
        cols |= bit_c << lvl
    label = rng.permutation(n)
    rows, cols = label[rows], label[cols]
    keep = rows != cols
    rows, cols = rows[keep], cols[keep]
    r = np.concatenate([rows, cols, np.arange(n)])
    q = np.concatenate([cols, rows, np.arange(n)])
    key = np.unique(r * n + q)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=indptr[1:])
    return indptr, (key % n).astype(np.int32)
