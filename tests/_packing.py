"""Sp×Sp operands packed the serving path's way
(``ops.pack_spgemm_pattern``) at the small tile widths the
interpret-mode tests use, and the route budgets those tests steer."""
import contextlib
from unittest import mock

import numpy as np

from repro.core.formats import HostCSR
from repro.kernels import ops


def pack(a, b, *, block_k, bn, block_r=8, **budgets_and_kw):
    """``ops.pack_spgemm_pattern(a, b)`` with B's tiles ``bn`` columns
    wide (the serving path packs 128) and A's row blocks ``block_r``
    high: 8 unless given, ``None`` for the height the pack selects from
    the pattern. Keywords named after a module constant of ``ops``
    (``_RESIDENT_B_BUDGET``, ``_SPARSE_C_DENSITY``, …) set it for the
    pack; the others go to the pack itself."""
    budgets = {k: v for k, v in budgets_and_kw.items() if k.startswith("_")}
    kw = {k: v for k, v in budgets_and_kw.items() if not k.startswith("_")}
    if block_r is not None:
        budgets["_BLOCK_R_HEIGHTS"] = (block_r,)
    with contextlib.ExitStack() as stack:
        for name, value in {"_BN": bn, **budgets}.items():
            stack.enter_context(mock.patch.object(ops, name, value))
        return ops.pack_spgemm_pattern(a, b, block_k=block_k, **kw)


def product(pattern, a, b) -> np.ndarray:
    """The pattern's dense product on the values of ``a`` and ``b``."""
    return np.asarray(pattern.run(*pattern.fill(a.data, b.data)))


def grouped(n, g, block_k, seed, *, integer=False) -> HostCSR:
    """An ``n × n`` pattern whose rows come in groups of ``g``, group
    ``q`` filling the two k-tiles ``2q`` and ``2q + 1`` (mod the tile
    count) at random, every row in both: with 16 tiles or more, the
    groups of a 128-row block share no tile, so a row block taller than
    ``g`` holds more tiles, each no fuller, and one shorter only splits
    the group's. The pack selects height ``g`` for it
    (``ops.select_block_r``). ``integer`` draws values from 1 to 4,
    whose products fp32 holds exactly."""
    rng = np.random.default_rng(seed)
    nk = n // block_k
    d = np.zeros((n, n), np.float32)
    for q in range(n // g):
        rows = slice(q * g, (q + 1) * g)
        for t in (2 * q % nk, (2 * q + 1) % nk):
            vals = (rng.integers(1, 5, (g, block_k)) if integer
                    else rng.uniform(0.5, 1.5, (g, block_k)))
            keep = rng.random((g, block_k)) < 0.3
            keep[:, 0] = True
            d[rows, t * block_k:(t + 1) * block_k] = keep * vals
    return HostCSR.from_dense(d)


def merge(n, block_k, seed) -> HostCSR:
    """An ``n × n/2`` operand whose k-tiles ``2t`` and ``2t + 1`` both
    land in column tile ``t``: each C window of ``grouped(...) @ merge``
    sums the products of two k-tiles."""
    rng = np.random.default_rng(seed)
    rows = np.arange(n)
    cols = rows // (2 * block_k) * block_k + rows % block_k
    d = np.zeros((n, n // 2), np.float32)
    d[rows, cols] = rng.integers(1, 5, n)
    return HostCSR.from_dense(d)


def scipy_product(*mats) -> np.ndarray:
    """The dense float64 ``scipy.sparse`` product of ``mats``, in order."""
    import scipy.sparse as sp
    out = None
    for m in mats:
        m64 = sp.csr_matrix((np.asarray(m.data, np.float64), m.indices,
                             m.indptr), shape=m.shape)
        out = m64 if out is None else out @ m64
    return out.toarray()
