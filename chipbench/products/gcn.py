"""GCN feature propagation Y = Â·X on a Graph500 Kronecker graph.

Â = D^-1/2 (A + I) D^-1/2 over the binary pattern of the graph (its
self loops are the + I), in fp32; its values depend on the pattern only,
so they stay fixed. Every request brings new features X (``features``
columns, standard normal fp32) drawn from the run's seed.

The plain reference is ``scipy.sparse`` in float64 on the fp32 Â and X
the program was sent. Compared, for each sampled response:

- ``max_err``: the largest |Y - ref| divided, entry by entry, by
  (|Â|·|X|), the scale of fp32 rounding in that sum of products. Where
  that scale is 0 (an isolated vertex whose feature is exactly 0) the
  answer has to be exactly 0.
"""
from __future__ import annotations

import numpy as np

from chipbench.generator import rng
from chipbench.graphs import kronecker_pattern
from chipbench.work import spmm_work

WORKLOAD = "spmm"
KERNEL = "spmm"


class Deployment:
    def __init__(self, cfg: dict, seed: int, traffic: dict):
        from repro.core.formats import HostCSR
        import scipy.sparse as sp
        self.cfg = cfg
        self.seed = int(seed)
        self.indptr, self.indices = kronecker_pattern(
            cfg["scale"], cfg["edgefactor"], cfg["graph_seed"],
            **cfg["initiator"])
        self.n = len(self.indptr) - 1
        self.nnz = len(self.indices)
        self.feats = int(cfg["features"])
        deg = np.diff(self.indptr).astype(np.float64)
        rows = np.repeat(np.arange(self.n), np.diff(self.indptr))
        vals = (deg[rows] ** -0.5) * (deg[self.indices] ** -0.5)
        self.operand = HostCSR(self.indptr, self.indices,
                               vals.astype(np.float32), (self.n, self.n))
        self._a64 = sp.csr_matrix(
            (self.operand.data.astype(np.float64), self.indices,
             self.indptr), shape=(self.n, self.n))

    def payload(self, k: int, *, warm: bool = False):
        x = rng(self.seed, 2 if warm else 1, k).standard_normal(
            (self.n, self.feats), dtype=np.float32)
        return self.operand, x

    def check(self, sample: list) -> dict:
        worst = 0.0
        abs_a = abs(self._a64)
        for (_, x), y in sample:
            x64 = x.astype(np.float64)
            ref = self._a64 @ x64
            scale = abs_a @ np.abs(x64)
            y = np.asarray(y, dtype=np.float64)
            if y.shape != ref.shape:
                return {"max_err": float("inf")}
            diff = np.abs(y - ref)
            e = float(np.max(np.divide(
                diff, scale, out=np.where(diff > 0, np.inf, 0.0),
                where=scale > 0)))
            if not np.isfinite(e):
                return {"max_err": float("inf")}
            worst = max(worst, e)
        return {"max_err": worst}

    def work(self) -> dict:
        return {KERNEL: spmm_work(self.n, self.n, self.nnz, self.feats)}


def control(dep: Deployment, payload, matmul=None):
    """The reference in the program's place at the precision below
    fp32-at-highest (three bf16 passes, fp32 accumulation), on the
    chip."""
    import jax.numpy as jnp
    from chipbench.lowprec import matmul_bf16x3
    matmul = matmul or matmul_bf16x3
    _, x = payload
    if getattr(dep, "_dense", None) is None:     # Â is the same each time
        dep._dense = jnp.asarray(dep._a64.astype(np.float32).toarray())
    return np.asarray(matmul(dep._dense, jnp.asarray(x)))
