"""A·A on a Graph500 Kronecker graph: the graph-analytics product.

The pattern comes from the configuration's ``graph_seed``, so every run
of a cell does the same work; every request brings a new value set on
that pattern, drawn from the run's seed (iterative analytics, AMG
re-set-up: the pattern stays, the values change).

The plain reference is ``scipy.sparse`` in float64 on the values the
program was sent. Compared, for each sampled response (dense C):

- ``max_err``: the largest |C - ref| / |ref| over ref's pattern (the
  values are positive, so |ref| is |A|·|A|, the scale of fp32 rounding
  at that entry);
- ``off_pattern``: the largest |C| off ref's pattern, which is exactly
  0 in exact arithmetic and in any sum of products of zeros.
"""
from __future__ import annotations

import numpy as np

from chipbench.generator import rng
from chipbench.graphs import kronecker_pattern
from chipbench.work import spgemm_work

WORKLOAD = "a2"
KERNEL = "sxs"


def _csr(indptr, indices, data, n):
    import scipy.sparse as sp
    return sp.csr_matrix((np.asarray(data, np.float64), indices, indptr),
                         shape=(n, n))


class Deployment:
    def __init__(self, cfg: dict, seed: int, traffic: dict):
        from repro.core.formats import HostCSR
        self._host = HostCSR
        self.cfg = cfg
        self.seed = int(seed)
        self.indptr, self.indices = kronecker_pattern(
            cfg["scale"], cfg["edgefactor"], cfg["graph_seed"],
            **cfg["initiator"])
        self.n = len(self.indptr) - 1
        self.nnz = len(self.indices)
        if cfg["values"][0] < 0:
            raise ValueError("the comparison needs positive values")
        self.operand = self._matrix(0)

    def _matrix(self, *stream: int):
        lo, hi = self.cfg["values"]
        values = rng(self.seed, *stream).uniform(lo, hi, self.nnz)
        return self._host(self.indptr, self.indices,
                          values.astype(np.float32), (self.n, self.n))

    def payload(self, k: int, *, warm: bool = False):
        """Request ``k``'s operands ``(a, b)``; a warm-up request never
        shares a value set with a measured one."""
        return self._matrix(2 if warm else 1, k), None

    def check(self, sample: list) -> dict:
        """``sample``: ``[(payload, result)]``. Returns each number
        compared, the worst over the sample."""
        worst = {"max_err": 0.0, "off_pattern": 0.0}
        refs = {}
        for (a, _), c in sample:
            if id(a) not in refs:
                a64 = _csr(self.indptr, self.indices, a.data, self.n)
                ref = (a64 @ a64).tocsr()
                ref.sort_indices()
                refs[id(a)] = ref
            e, off = _compare_dense(np.asarray(c), refs[id(a)])
            worst["max_err"] = max(worst["max_err"], e)
            worst["off_pattern"] = max(worst["off_pattern"], off)
        return worst

    def work(self) -> dict:
        import scipy.sparse as sp
        pat = sp.csr_matrix((np.ones(self.nnz, np.float32), self.indices,
                             self.indptr), shape=(self.n, self.n))
        nnz_c = (pat @ pat).nnz
        return {KERNEL: spgemm_work(self.indptr, self.indices, self.indptr,
                                    self.nnz, nnz_c)}


def _compare_dense(c: np.ndarray, ref, rows_per_block: int = 1024
                   ) -> tuple[float, float]:
    """(max_err, off_pattern) of dense ``c`` against scipy ``ref``, one
    block of rows at a time so that no second dense copy is made.
    NaN anywhere reads as infinity."""
    if c.shape != ref.shape:
        return float("inf"), float("inf")
    err, off = 0.0, 0.0
    for r0 in range(0, c.shape[0], rows_per_block):
        r1 = min(r0 + rows_per_block, c.shape[0])
        blk = np.array(c[r0:r1], dtype=np.float64)
        lo, hi = ref.indptr[r0], ref.indptr[r1]
        rows = np.repeat(np.arange(r1 - r0), np.diff(ref.indptr[r0:r1 + 1]))
        cols = ref.indices[lo:hi]
        want = ref.data[lo:hi]
        d = float(np.max(np.abs(blk[rows, cols] - want) / want, initial=0.0))
        blk[rows, cols] = 0.0
        o = float(np.max(np.abs(blk), initial=0.0))
        if not (np.isfinite(d) and np.isfinite(o)):
            return float("inf"), float("inf")
        err, off = max(err, d), max(off, o)
    return err, off


def control(dep: Deployment, payload, matmul=None):
    """The reference in the program's place at the precision below the
    configuration's fp32-at-highest: three bf16 passes (as the MXU's
    ``precision=HIGH``), accumulated in fp32, on the chip. Dense C."""
    import jax.numpy as jnp
    from chipbench.lowprec import matmul_bf16x3
    matmul = matmul or matmul_bf16x3
    a, _ = payload
    dense = _csr(dep.indptr, dep.indices, a.data, dep.n).astype(
        np.float32).toarray()
    d = jnp.asarray(dense)
    del dense
    return np.asarray(matmul(d, d))
