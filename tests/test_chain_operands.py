"""Chains of distinct, rectangular operands: AMG's Galerkin product
``R·A·P`` served as ``submit(R, (A, P))``.

A is a 27-point operator on an N³ grid with random symmetric weights, P
the smoothed-aggregation prolongator of its 3×3×3 aggregates and R = Pᵀ.
Checked against float64 scipy: the answer, the association the planner
picks, the pattern-keyed refill of both hops, ``hops=k`` as the operand
chain ``(A,)*k``, the shape-chain validation, and the bytes the ``carry``
and ``fetch`` spans report.
"""
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core.formats import HostCSR
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs.trace import get_tracer
from repro.planner import service
from repro.planner.cost_model import Candidate
from repro.planner.features import fingerprint
from repro.planner.plan_cache import Plan, PlanCache
from repro.planner.service import Planner, chain_order
from repro.resilience.errors import InvalidOperandError
from repro.serve.engine import SpGEMMServer
from repro.serve.frontend import AsyncSpGEMMServer

PALLAS = Candidate("original", "pallas")


def _galerkin_patterns(n_side: int):
    """A's 27-point pattern and the aggregate of each point."""
    line = sp.diags([1.0, 1.0, 1.0], [-1, 0, 1], shape=(n_side, n_side))
    pat = sp.kron(sp.kron(line, line), line).tocsr()
    pts = np.arange(n_side ** 3)
    x, y, z = pts % n_side, pts // n_side % n_side, pts // n_side ** 2
    nc = -(-n_side // 3)
    agg = x // 3 + nc * (y // 3) + nc * nc * (z // 3)
    return pat, agg, nc ** 3


def _host(m) -> HostCSR:
    m = m.tocsr()
    m.sort_indices()
    return HostCSR(m.indptr, m.indices, m.data.astype(np.float32), m.shape)


def _galerkin(n_side: int, seed: int):
    """``(R, A, P)`` as fp32 HostCSR on fixed patterns, values from
    ``seed``."""
    pat, agg, n_c = _galerkin_patterns(n_side)
    rng = np.random.default_rng(seed)
    w = sp.triu(pat, k=1).tocsr()
    w.data = rng.uniform(0.5, 1.5, w.nnz)
    w = w + w.T
    extra = rng.uniform(0.0, 2.0, pat.shape[0])
    a = (sp.diags(np.asarray(w.sum(axis=1)).ravel() + extra) - w).tocsr()
    a = _host(a)
    a64 = sp.csr_matrix((a.data.astype(np.float64), a.indices, a.indptr),
                        shape=a.shape)
    t = sp.csr_matrix((np.ones(a.nrows), agg, np.arange(a.nrows + 1)),
                      shape=(a.nrows, n_c))
    t = t.multiply(1.0 / np.sqrt(np.asarray(t.sum(axis=0)))).tocsr()
    p = (t - (2.0 / 3.0) * sp.diags(1.0 / a64.diagonal()) @ a64 @ t)
    p = _host(p)
    r = _host(sp.csr_matrix((p.data, p.indices, p.indptr),
                            shape=p.shape).T)
    return r, a, p


def _rel_err(c: HostCSR, *mats) -> float:
    """The largest |C - ref| over |R|·|A|·|P|, entry by entry (0 where
    both are 0)."""
    diff = np.abs(c.to_dense() - _ref(*mats))
    scale = _scale(*mats)
    return float(np.max(np.divide(diff, scale, out=np.where(
        diff > 0, np.inf, 0.0), where=scale > 0)))


def _ref(*mats):
    out = None
    for m in reversed(mats):
        m64 = sp.csr_matrix((m.data.astype(np.float64), m.indices,
                             m.indptr), shape=m.shape)
        out = m64 if out is None else m64 @ out
    return out.toarray()


def _scale(*mats):
    out = None
    for m in reversed(mats):
        m64 = sp.csr_matrix((np.abs(m.data.astype(np.float64)), m.indices,
                             m.indptr), shape=m.shape)
        out = m64 if out is None else m64 @ out
    return out.toarray()


def _pallas_server(a: HostCSR, monkeypatch) -> AsyncSpGEMMServer:
    """The benchmark's server: A's Pallas plan cached, R planned by the
    planner from one candidate (Pallas, since R's hop would otherwise
    return a dense C over the patched budget)."""
    monkeypatch.setattr(service, "_CHAIN_DENSE_C_BUDGET", 0)
    planner = Planner(cache=PlanCache(), candidates=(PALLAS,))
    planner.cache.put(Plan(fingerprint=fingerprint(a), reorder="original",
                           scheme="pallas", reuse_hint=20, workload="a2"))
    return AsyncSpGEMMServer(SpGEMMServer(planner=planner), workers=1)


@pytest.mark.parametrize("n_side", [6, 9, 12])
def test_galerkin_product_matches_float64_scipy(n_side, monkeypatch):
    r, a, p = _galerkin(n_side, seed=n_side)
    srv = _pallas_server(a, monkeypatch)
    try:
        resp = srv.submit(r, (a, p), reuse_hint=20).result(300)
    finally:
        srv.close()
    assert resp.workload == "chain" and resp.kernel_path == "pallas"
    assert isinstance(resp.result, HostCSR)
    assert resp.result.shape == (r.nrows, p.ncols)
    assert _rel_err(resp.result, r, a, p) < 4e-6


def test_association_keeps_the_intermediate_narrow():
    """At one rank's size only R·(A·P) keeps its intermediate within the
    sparse-C tier's C row strip: (R·A) has a column per fine point."""
    n, n_c = 102 ** 3, 34 ** 3
    shapes = [(n_c, n), (n, n), (n, n_c)]
    nnzs = [4_600_000, 27_700_000, 4_600_000]
    assert chain_order(shapes, nnzs) == (0, (1, 2))
    assert ops.compact_grid_ok_ncols(n_c)
    assert not ops.compact_grid_ok_ncols(n)
    # equal estimated flops: the narrower intermediate still wins
    assert chain_order([(8, 64), (64, 64), (64, 8)], [64, 512, 64]) \
        == (0, (1, 2))
    assert chain_order([(64, 64)] * 3, [256] * 3) == ((0, 1), 2)


def test_served_chain_runs_r_times_a_p(monkeypatch):
    r, a, p = _galerkin(6, seed=1)
    srv = _pallas_server(a, monkeypatch)
    try:
        srv.submit(r, (a, p), reuse_hint=20).result(300)
    finally:
        srv.close()
    planner = srv.server.planner
    # hop 1 is A·P (A's cached plan), hop 2 R·(AP): two sparse-C entries
    keys = list(planner._exec_cache)
    assert len(keys) == 2
    assert keys[0].startswith(fingerprint(a)) and "|chain|ab|" in keys[0]
    assert keys[0].endswith(fingerprint(p))
    assert keys[1].startswith(fingerprint(r))
    assert all(v[0] == "chain" for v in planner._exec_cache.values())


def test_new_values_refill_each_hop_without_a_pack(monkeypatch):
    srv = _pallas_server(_galerkin(6, seed=3)[1], monkeypatch)
    reg = obs_metrics.get_registry()
    try:
        for seed in (3, 4, 5):
            r, a, p = _galerkin(6, seed=seed)
            packs = reg.counter("exec_cache_packs").value
            refills = reg.counter("exec_cache_refills").value
            resp = srv.submit(r, (a, p), reuse_hint=20).result(300)
            assert _rel_err(resp.result, r, a, p) < 4e-6
            if seed == 3:
                entries = len(srv.server.planner._exec_cache)
                continue
            # both hops refilled on the device: no pack, no new entry
            assert reg.counter("exec_cache_packs").value == packs
            assert reg.counter("exec_cache_refills").value == refills + 2
            assert len(srv.server.planner._exec_cache) == entries
    finally:
        srv.close()


def test_hops_is_the_chain_of_the_same_operand():
    rng = np.random.default_rng(7)
    a = HostCSR.from_dense(((rng.random((40, 40)) < 0.08)
                            * rng.integers(1, 5, (40, 40))).astype(
                                np.float32))
    srv = AsyncSpGEMMServer(SpGEMMServer(Planner()), workers=1)
    try:
        by_hops = srv.submit(a, hops=2).result(300)
        by_operands = srv.submit(a, (a, a)).result(300)
    finally:
        srv.close()
    assert by_hops.workload == by_operands.workload == "chain"
    d = a.to_dense()
    np.testing.assert_array_equal(by_hops.result.to_dense(), d @ d @ d)
    np.testing.assert_array_equal(by_operands.result.to_dense(),
                                  by_hops.result.to_dense())


def test_operands_that_do_not_chain_are_rejected():
    r, a, p = _galerkin(6, seed=2)
    srv = AsyncSpGEMMServer(SpGEMMServer(Planner()), workers=1)
    try:
        with pytest.raises(InvalidOperandError) as e:
            srv.submit(r, (p, a)).result(60)       # (n_c, n)·(n, n_c)·(n, n)
    finally:
        srv.close()
    assert e.value.field == "shape"
    with pytest.raises(InvalidOperandError):
        SpGEMMServer(Planner()).submit(a, (r,))    # a.ncols != r.nrows
    with pytest.raises(InvalidOperandError):
        SpGEMMServer(Planner()).submit(r, ())


def test_wide_hop_is_planned_on_pallas(monkeypatch):
    """A hop whose XLA route would densify a C over the budget takes the
    Pallas candidate even where it does not amortize (as off the chip)."""
    r, _, p = _galerkin(6, seed=2)
    planner = Planner(cache=PlanCache(), candidates=(PALLAS,))
    assert planner.plan(r, 20, out_cols=p.ncols).scheme == "rowwise"
    monkeypatch.setattr(service, "_CHAIN_DENSE_C_BUDGET", 0)
    planner = Planner(cache=PlanCache(), candidates=(PALLAS,))
    assert planner.plan(r, 20, out_cols=p.ncols).scheme == "pallas"


def test_carry_and_fetch_spans_carry_the_bytes_moved(monkeypatch):
    r, a, p = _galerkin(6, seed=5)
    srv = _pallas_server(a, monkeypatch)
    tracer = get_tracer()
    reg = obs_metrics.get_registry()
    srv.submit(r, (a, p), reuse_hint=20).result(300)    # packs both hops
    r2, a2, p2 = _galerkin(6, seed=6)
    carried = reg.counter("chain_carry_bytes").value
    tracer.clear()
    tracer.enable()
    try:
        resp = srv.submit(r2, (a2, p2), reuse_hint=20).result(300)
    finally:
        tracer.disable()
        srv.close()
    spans = tracer.spans()
    tracer.clear()
    ap_nnz = int((abs(_scale(a2, p2)) > 0).sum())
    carry = [s for s in spans if s.name == "carry"]
    assert len(carry) == 1
    # AP's values come down once and go up again in hop 2's refill
    assert carry[0].attrs["bytes"] == 2 * 4 * ap_nnz
    assert reg.counter("chain_carry_bytes").value == carried + 8 * ap_nnz
    by_id = {s.span_id: s for s in spans}
    inside = [s for s in spans if s.parent_id == carry[0].span_id]
    assert {s.name for s in inside} >= {"fetch", "pack"}
    assert [s.attrs["kind"] for s in inside if s.name == "pack"] == \
        ["refill"]
    fetches = [s for s in spans if s.name == "fetch"]
    assert sorted(s.attrs["bytes"] for s in fetches) == sorted(
        [4 * ap_nnz, 4 * resp.result.nnz])
    # the result's fetch and guard run in the last hop, not in a carry
    last = [s for s in fetches if s.parent_id != carry[0].span_id]
    assert len(last) == 1 and by_id[last[0].parent_id].name == "hop"
    assert any(s.name == "guard" for s in spans)
