"""The Galerkin triple product R·A·P of algebraic-multigrid set-up.

A is the 27-point operator of a 3-D Poisson problem with variable
coefficients on one rank's N³ box (N = round(2**(scale/3))), Dirichlet
boundary, points numbered x fastest: the off-diagonal of each pair of
neighbours is -w, w symmetric and U(0.5, 1.5), and the diagonal sums
the weights of all 26 neighbours, those outside the box included (the
halo couplings another rank would hold). P is the smoothed-aggregation
prolongator: the box cut into ``aggregate``³ aggregates, the tentative
prolongator T with unit-norm columns, and one damped-Jacobi step
P = (I - ω D⁻¹A)·T with ω = (4/3)/ρ and ρ = 2, the Gershgorin bound of
ρ(D⁻¹A). R = Pᵀ.

The patterns come from the configuration alone; every request brings
new coefficients, drawn from the run's seed, so A, P and R all carry
new values on their fixed patterns (re-set-up at every time step or
Newton step). P's and R's values come from A's through pattern maps
built once: one segmented sum over A's nonzeros and a fixed
permutation.
A request is ``submit(R, (A, P))``.

The plain reference is ``scipy.sparse`` in float64, ``R @ (A @ P)`` on
the fp32 values the program was sent. Compared, for each sampled
response (a sparse C):

- ``max_err``: the largest |C - ref| divided, entry by entry, by
  (|R|·|A|·|P|), the scale of fp32 rounding in that sum of products (A
  has negative entries and R·A·P cancels, so |ref| is no scale);
- ``off_pattern``: the largest |C| off the symbolic pattern of R·A·P,
  exactly 0 in any sum of products of zeros.

A C of another shape reads as infinity in both.
"""
from __future__ import annotations

import numpy as np

from chipbench.generator import rng
from chipbench.work import spgemm_work

WORKLOAD = "a2"
KERNEL = "rap"

# the 13 neighbour offsets (dx, dy, dz) that come after (0, 0, 0) with z
# the slowest coordinate; each pair of neighbours is one of them apart
FORWARD = [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
           for dx in (-1, 0, 1) if (dz, dy, dx) > (0, 0, 0)]


def grid_side(scale: int) -> int:
    return int(round(2 ** (scale / 3)))


def _sp():
    import scipy.sparse as sp
    return sp


class Deployment:
    def __init__(self, cfg: dict, seed: int, traffic: dict):
        from repro.core.formats import HostCSR
        sp = _sp()
        self._host = HostCSR
        self.cfg = cfg
        self.seed = int(seed)
        n_side = grid_side(cfg["scale"])
        agg = int(cfg["aggregate"])
        self.omega = (4.0 / 3.0) / float(cfg["rho"])
        self.N = n_side
        self.n = n_side ** 3
        e = n_side + 2                    # the box with one halo layer
        self._ext = e
        pts = np.arange(self.n, dtype=np.int64)
        x, y, z = pts % n_side, pts // n_side % n_side, pts // n_side ** 2
        ext = (x + 1) + e * (y + 1) + e * e * (z + 1)

        rows, cols, widx = [np.arange(self.n)], [np.arange(self.n)], \
            [np.full(self.n, -1, np.int64)]
        diag_terms = []
        for d, (dx, dy, dz) in enumerate(FORWARD):
            step = dx + e * dy + e * e * dz
            # the pair {p, p + off} has weight W[d, ext(p)], and the pair
            # {p - off, p} weight W[d, ext(p) - step]
            diag_terms += [d * e ** 3 + ext, d * e ** 3 + ext - step]
            for sgn in (1, -1):
                qx, qy, qz = x + sgn * dx, y + sgn * dy, z + sgn * dz
                inside = ((qx >= 0) & (qx < n_side) & (qy >= 0)
                          & (qy < n_side) & (qz >= 0) & (qz < n_side))
                p = np.flatnonzero(inside)
                q = qx[p] + n_side * qy[p] + n_side ** 2 * qz[p]
                rows.append(p)
                cols.append(q)
                widx.append(d * e ** 3 + (ext[p] if sgn == 1
                                          else ext[p] - step))
        rows, cols, widx = (np.concatenate(v) for v in (rows, cols, widx))
        key = rows * self.n + cols
        o = np.argsort(key)
        rows, cols, widx = rows[o], cols[o], widx[o]
        self._a_off = np.flatnonzero(widx >= 0)
        self._a_off_w = widx[self._a_off]
        self._a_diag = np.flatnonzero(widx < 0)       # one a row, in order
        self._diag_w = np.stack(diag_terms)           # (26, n)
        indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=self.n), out=indptr[1:])
        self.a_indptr, self.a_indices = indptr, cols.astype(np.int32)
        self.nnz_a = len(cols)

        # the aggregates and the tentative prolongator's column weights
        nc_side = -(-n_side // agg)
        self.n_c = nc_side ** 3
        aggs = (x // agg) + nc_side * (y // agg) + nc_side ** 2 * (z // agg)
        t = 1.0 / np.sqrt(np.bincount(aggs, minlength=self.n_c)[aggs])
        # P's pattern is that of A·T: the A nonzero (p, k) adds to
        # P[p, agg(k)]; in the order p_order, each P value is one
        # segment of A's nonzeros
        pkey = rows * self.n_c + aggs[cols]
        self._p_order = np.argsort(pkey, kind="stable")
        pkey = pkey[self._p_order]
        self._p_seg = np.flatnonzero(np.diff(pkey, prepend=-1))
        ukey = pkey[self._p_seg]
        p_rows = ukey // self.n_c
        self.p_indices = (ukey % self.n_c).astype(np.int32)
        self.nnz_p = len(ukey)
        self.p_indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(p_rows, minlength=self.n),
                  out=self.p_indptr[1:])
        self._p_rows = p_rows
        self._t_cols = t[cols[self._p_order]].astype(np.float32)
        self._p_tent = np.searchsorted(
            ukey, np.arange(self.n, dtype=np.int64) * self.n_c + aggs)
        self._t, self._agg = t, aggs
        # R = Pᵀ: R's k-th nonzero is P's nonzero r_src[k]
        tr = sp.csr_matrix((np.arange(self.nnz_p, dtype=np.float64),
                            self.p_indices, self.p_indptr),
                           shape=(self.n, self.n_c)).T.tocsr()
        tr.sort_indices()
        self.r_indptr = tr.indptr.astype(np.int64)
        self.r_indices = tr.indices.astype(np.int32)
        self._r_src = tr.data.astype(np.int64)
        self.operand = self._matrices(0)[1]

    def _matrices(self, *stream: int):
        """``(R, A, P)`` for the coefficients of one stream: A from the
        drawn weights, P and R from A's values through the pattern maps,
        all fp32 as they are sent."""
        e3 = self._ext ** 3
        lo, hi = self.cfg["weights"]
        w = rng(self.seed, *stream).random(len(FORWARD) * e3,
                                           dtype=np.float32)
        w = lo + (hi - lo) * w
        a = np.empty(self.nnz_a, np.float32)
        a[self._a_off] = -w[self._a_off_w]
        d = w[self._diag_w].sum(axis=0, dtype=np.float64)
        a[self._a_diag] = d
        # P = T - ω D⁻¹ A T, from the fp32 A that is sent
        p = np.add.reduceat(a[self._p_order] * self._t_cols, self._p_seg,
                            dtype=np.float64)
        p *= (-self.omega / a[self._a_diag].astype(np.float64))[self._p_rows]
        p[self._p_tent] += self._t
        p = p.astype(np.float32)
        host = self._host
        return (host(self.r_indptr, self.r_indices, p[self._r_src],
                     (self.n_c, self.n)),
                host(self.a_indptr, self.a_indices, a, (self.n, self.n)),
                host(self.p_indptr, self.p_indices, p, (self.n, self.n_c)))

    def payload(self, k: int, *, warm: bool = False):
        """Request ``k``'s operands ``(R, (A, P))``; a warm-up request
        never shares a value set with a measured one."""
        r, a, p = self._matrices(2 if warm else 1, k)
        return r, (a, p)

    def _scipy(self, h, absolute: bool = False):
        data = h.data.astype(np.float64)
        return _sp().csr_matrix((np.abs(data) if absolute else data,
                                 h.indices, h.indptr), shape=h.shape)

    def reference(self, payload) -> tuple:
        """``(ref, scale)``: R·(A·P) and |R|·(|A|·|P|) in float64."""
        r, (a, p) = payload
        ref = self._scipy(r) @ (self._scipy(a) @ self._scipy(p))
        scale = self._scipy(r, True) @ (self._scipy(a, True)
                                        @ self._scipy(p, True))
        return ref.tocsr(), scale.tocsr()

    def check(self, sample: list) -> dict:
        """``sample``: ``[(payload, result)]``. Returns each number
        compared, the worst over the sample. The samples are compared on
        a few threads (scipy's products release the GIL)."""
        import os
        from concurrent.futures import ThreadPoolExecutor

        def one(item):
            payload, c = item
            return compare_sparse(c, *self.reference(payload))
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            got = list(pool.map(one, sample))
        return {"max_err": max((e for e, _ in got), default=0.0),
                "off_pattern": max((o for _, o in got), default=0.0)}

    def _pattern(self, indptr, indices, shape):
        return _sp().csr_matrix((np.ones(len(indices), np.float32),
                                 indices, indptr), shape=shape)

    def work(self) -> dict:
        """Both hops, A·P and R·(AP): their flops and CSR bytes."""
        a = self._pattern(self.a_indptr, self.a_indices, (self.n, self.n))
        p = self._pattern(self.p_indptr, self.p_indices, (self.n, self.n_c))
        r = self._pattern(self.r_indptr, self.r_indices, (self.n_c, self.n))
        ap = (a @ p).tocsr()
        nnz_c = (r @ ap).nnz
        f1, b1 = spgemm_work(self.a_indptr, self.a_indices, self.p_indptr,
                             self.nnz_p, ap.nnz)
        f2, b2 = spgemm_work(self.r_indptr, self.r_indices, ap.indptr,
                             ap.nnz, nnz_c)
        return {KERNEL: (f1 + f2, b1 + b2)}


def compare_sparse(c, ref, scale) -> tuple[float, float]:
    """(max_err, off_pattern) of a sparse ``c`` (``HostCSR``) against
    scipy ``ref``, over ``scale``'s pattern, the symbolic one. A ``c`` of
    another shape, or anything not finite, reads as infinity."""
    sp = _sp()
    if getattr(c, "shape", None) != ref.shape:
        return float("inf"), float("inf")
    got = sp.csr_matrix((np.asarray(c.data, np.float64), c.indices,
                         c.indptr), shape=c.shape)
    mask = scale.copy()
    mask.data[:] = 1.0
    on = got.multiply(mask).tocsr()
    off = abs(got - on)
    inv = scale.copy()
    inv.data = 1.0 / inv.data
    err = abs(on - ref).multiply(inv)
    e = float(err.max()) if err.nnz else 0.0
    o = float(off.max()) if off.nnz else 0.0
    if not (np.isfinite(e) and np.isfinite(o)) or not np.all(
            np.isfinite(c.data)):
        return float("inf"), float("inf")
    return e, o


def _bf16(x: np.ndarray) -> np.ndarray:
    """fp32 ``x`` rounded to bf16 (nearest, ties to even), as fp32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + (0x7FFF + ((u >> 16) & 1))) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _bf16x3(x, y):
    """``x @ y`` (scipy, fp32) in three bf16 passes: hi·hi + (hi·lo +
    lo·hi), fp32 accumulation, as ``lowprec.matmul_bf16x3`` does."""
    def split(m):
        hi = _bf16(m.data)
        lo = _bf16(m.data - hi)
        return (m.__class__((hi, m.indices, m.indptr), shape=m.shape),
                m.__class__((lo, m.indices, m.indptr), shape=m.shape))
    (xh, xl), (yh, yl) = split(x), split(y)
    return (xh @ yh + (xh @ yl + xl @ yh)).tocsr()


def control(dep: Deployment, payload, matmul=None):
    """The reference in the program's place at the precision below the
    configuration's fp32-at-highest: each hop in three bf16 passes with
    fp32 accumulation (scipy on the host: no dense form of these
    operands fits, so ``matmul`` is not used). A sparse C."""
    sp = _sp()
    r, (a, p) = payload

    def f32(h):
        return sp.csr_matrix((h.data, h.indices, h.indptr), shape=h.shape)
    c = _bf16x3(f32(r), _bf16x3(f32(a), f32(p)))
    c.sort_indices()
    return dep._host(c.indptr, c.indices, c.data.astype(np.float32),
                     c.shape)
