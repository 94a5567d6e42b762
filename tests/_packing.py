"""Sp×Sp operands packed the serving path's way
(``ops.pack_spgemm_pattern``) at the small tile widths the
interpret-mode tests use, and the route budgets those tests steer."""
import contextlib
from unittest import mock

import numpy as np

from repro.kernels import ops


def pack(a, b, *, block_k, bn, **budgets_and_kw):
    """``ops.pack_spgemm_pattern(a, b)`` with B's tiles ``bn`` columns
    wide (the serving path packs 128). Keywords named after a module
    constant of ``ops`` (``_RESIDENT_B_BUDGET``, ``_SPARSE_C_DENSITY``,
    …) set it for the pack; the others go to the pack itself."""
    budgets = {k: v for k, v in budgets_and_kw.items() if k.startswith("_")}
    kw = {k: v for k, v in budgets_and_kw.items() if not k.startswith("_")}
    with contextlib.ExitStack() as stack:
        for name, value in {"_BN": bn, **budgets}.items():
            stack.enter_context(mock.patch.object(ops, name, value))
        return ops.pack_spgemm_pattern(a, b, block_k=block_k, **kw)


def product(pattern, a, b) -> np.ndarray:
    """The pattern's dense product on the values of ``a`` and ``b``."""
    return np.asarray(pattern.run(*pattern.fill(a.data, b.data)))
