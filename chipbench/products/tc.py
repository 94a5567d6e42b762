"""Triangle counting's masked square C = (L·L)∘L on a Graph500 Kronecker
graph, the GraphChallenge formulation in linear algebra.

The graph is the Graph500 generator's (``graph_seed``), symmetrized with
its self loops. Its vertices are relabelled by descending degree, ties
by id, as GAP's ``tc`` relabels a skewed graph, and L is the lower
triangle of the relabelled pattern, diagonal kept: every edge points
toward its higher-degree end. Every request brings new edge weights on
that fixed L, drawn from the run's seed (weighted triangle intensity,
recomputed per time window on a fixed topology), and asks for
``submit(L, Masked(None, L))``: the product's values on L's pattern,
times L's.

The plain reference is ``scipy.sparse`` in float64, ``(L @ L)``
multiplied entry by entry by ``L``, on the fp32 values the program was
sent. Compared, for each sampled response (a sparse C):

- ``max_err``: the largest |C - ref| divided, entry by entry, by
  (|L|·|L|)∘|L|, the scale of fp32 rounding in that sum of products (the
  weights are positive, so that scale is ref itself);
- ``off_pattern``: the largest |C| off L's pattern, exactly 0 for an
  answer on the mask's pattern; infinity when C's pattern is not
  exactly L's (the answer of a masked product is on the mask's
  pattern, with explicit zeros where no product term lands).

A C of another shape reads as infinity in both.
"""
from __future__ import annotations

import numpy as np

from chipbench.generator import rng
from chipbench.graphs import kronecker_pattern
from chipbench.products.rap import _bf16x3, compare_sparse
from chipbench.work import csr_bytes

WORKLOAD = "a2"
KERNEL = "tc"


def degree_ordered_lower(indptr, indices) -> tuple[np.ndarray, np.ndarray]:
    """CSR ``(indptr, indices)`` of the lower triangle, diagonal kept, of
    the symmetric pattern after relabelling its vertices by descending
    degree (ties by the old id): vertex 0 is the largest hub."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    label = np.empty(n, np.int64)
    label[np.lexsort((np.arange(n), -deg))] = np.arange(n)
    rows = label[np.repeat(np.arange(n), deg)]
    cols = label[np.asarray(indices, np.int64)]
    keep = cols <= rows
    key = np.sort(rows[keep] * n + cols[keep])
    out = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=out[1:])
    return out, (key % n).astype(np.int32)


class Deployment:
    def __init__(self, cfg: dict, seed: int, traffic: dict):
        from repro.core.formats import HostCSR, Masked
        self._host, self._masked = HostCSR, Masked
        self.cfg = cfg
        self.seed = int(seed)
        self.indptr, self.indices = degree_ordered_lower(*kronecker_pattern(
            cfg["scale"], cfg["edgefactor"], cfg["graph_seed"],
            **cfg["initiator"]))
        self.n = len(self.indptr) - 1
        self.nnz = len(self.indices)
        if cfg["values"][0] <= 0:
            raise ValueError("the comparison needs positive values")
        self.operand = self._matrix(0)

    def _matrix(self, *stream: int):
        lo, hi = self.cfg["values"]
        values = rng(self.seed, *stream).uniform(lo, hi, self.nnz)
        return self._host(self.indptr, self.indices,
                          values.astype(np.float32), (self.n, self.n))

    def payload(self, k: int, *, warm: bool = False):
        """Request ``k``'s operands ``(L, Masked(None, L))``; a warm-up
        request never shares a value set with a measured one."""
        a = self._matrix(2 if warm else 1, k)
        return a, self._masked(None, a)

    def _scipy(self, data):
        import scipy.sparse as sp
        return sp.csr_matrix((np.asarray(data, np.float64), self.indices,
                              self.indptr), shape=(self.n, self.n))

    def reference(self, payload):
        """(L @ L) ∘ L in float64 on the values sent."""
        l64 = self._scipy(payload[0].data)
        return (l64 @ l64).multiply(l64).tocsr()

    def check(self, sample: list) -> dict:
        """``sample``: ``[(payload, result)]``. Returns each number
        compared, the worst over the sample. The samples are compared on
        a few threads (scipy's products release the GIL)."""
        import os
        from concurrent.futures import ThreadPoolExecutor

        def one(item):
            payload, c = item
            ref = self.reference(payload)
            e, off = compare_sparse(c, ref, ref)
            if getattr(c, "nnz", None) != self.nnz or not (
                    np.array_equal(c.indptr, self.indptr)
                    and np.array_equal(c.indices, self.indices)):
                off = float("inf")
            return e, off
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            got = list(pool.map(one, sample))
        return {"max_err": max((e for e, _ in got), default=0.0),
                "off_pattern": max((o for _, o in got), default=0.0)}

    def work(self) -> dict:
        """The masked square: two flops for each product of two stored
        nonzeros that lands on L's pattern, one for the mask's multiply;
        L read three times (two operands, the mask) and C written, as
        CSR on L's pattern."""
        ones = self._scipy(np.ones(self.nnz))
        in_mask = float((ones @ ones).multiply(ones).sum())
        return {KERNEL: (2 * int(in_mask) + self.nnz,
                         4 * csr_bytes(self.n, self.nnz))}


def control(dep: Deployment, payload, matmul=None):
    """The reference in the program's place at the precision below the
    configuration's fp32-at-highest: L·L in three bf16 passes with fp32
    accumulation, then times L in fp32 (scipy on the host, the scheme of
    ``lowprec.matmul_bf16x3``: no dense form of L fits the chip at the
    cell's size, so ``matmul`` is not used). A sparse C."""
    import scipy.sparse as sp
    a = payload[0]
    l32 = sp.csr_matrix((a.data, a.indices, a.indptr), shape=a.shape)
    c = _bf16x3(l32, l32).multiply(l32).tocsr()
    c.sort_indices()
    return dep._host(c.indptr, c.indices, c.data.astype(np.float32),
                     c.shape)
