"""The work one request asks of a kernel, and the least time the chip
could take for it: the yardstick of every ``<kernel>_roofline`` metric.

Useful flops count only products of two stored nonzeros. Compulsory
bytes read each operand once and write the result once, sparse matrices
in CSR (int32 row pointers, int32 column index and fp32 value per
nonzero) and dense ones as fp32 arrays. A sparse result counts its
nonzeros, whatever layout the program returns it in.
"""
from __future__ import annotations

import json
import os

import numpy as np

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def csr_bytes(nrows: int, nnz: int) -> int:
    return 4 * (nrows + 1) + 8 * nnz


def spgemm_flops(a_indices: np.ndarray, b_indptr: np.ndarray) -> int:
    """2 × Σ over A's nonzeros a_ik of nnz(B row k) (a copy of
    ``repro.core.spgemm.flops_spgemm``)."""
    b_row = np.diff(np.asarray(b_indptr, dtype=np.int64))
    return int(2 * b_row[np.asarray(a_indices, dtype=np.int64)].sum())


def spgemm_work(a_indptr, a_indices, b_indptr, b_nnz: int,
                nnz_c: int) -> tuple[int, int]:
    """(flops, bytes) of C = A·B with ``nnz_c`` nonzeros in C."""
    nrows = len(a_indptr) - 1
    return (spgemm_flops(a_indices, b_indptr),
            csr_bytes(nrows, len(a_indices))
            + csr_bytes(len(b_indptr) - 1, b_nnz) + csr_bytes(nrows, nnz_c))


def spmm_work(nrows: int, ncols: int, nnz: int, feats: int
              ) -> tuple[int, int]:
    """(flops, bytes) of Y = A·X with X of ``feats`` dense columns."""
    return (2 * nnz * feats,
            csr_bytes(nrows, nnz) + 4 * ncols * feats + 4 * nrows * feats)


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that
    is not in the table is an error, not a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peak: dict
                 ) -> tuple[float, str]:
    """The larger of compute time and memory time, and which bounds."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
