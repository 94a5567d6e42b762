"""Sparse-C two-phase pipeline (ISSUE 6): the symbolic per-strip nnz
upper bound (vectorized vs loop reference, domination over exact per-row
nnz(C), tightness on disjoint-column constructions), the ``CompactedC``
round trip (bit-identical to ``spgemm_reference`` for both sparse-C
kernel variants on integer-valued operands, at row blocks 8, 16 and 32
high), the density routing of ``ops.pack_spgemm_pattern``, and the
``workload="chain"`` planner path (A³ end-to-end with per-hop plan-cache
hits on the second call; two hops at the heights their patterns select).
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # pragma: no cover - container without hypothesis
    from _hypo_shim import given, settings, st

from repro.core.formats import (COUNTER_UNITS, HostCSR,
                                compacted_c_counters, compacted_c_from_dense,
                                compacted_c_table, compacted_c_to_host,
                                symbolic_strip_nnz,
                                symbolic_strip_nnz_reference,
                                tile_col_occupancy, tiled_csr_from_host)
from repro.core.spgemm import spgemm_reference, symbolic_row_nnz
from repro.kernels import ops
from repro.kernels.cluster_spgemm import (cluster_spgemm_pairs_sparse,
                                          cluster_spgemm_pairs_sparse_db)

from _packing import grouped, merge, pack, scipy_product

BR, BK, BN = 8, 16, 16


def int_host(n, m, density, seed):
    """Integer-valued random pattern: products are exactly representable
    in fp32, so kernel outputs must equal the reference bit for bit."""
    rng = np.random.default_rng(seed)
    dense = (rng.random((n, m)) < density) * rng.integers(
        1, 5, (n, m)).astype(np.float32)
    return HostCSR.from_dense(dense.astype(np.float32))


def _pack(a, b, **kw):
    """The sparse-C pattern of ``a @ b`` and its value set."""
    pattern = pack(a, b, block_k=BK, bn=BN, sparse_out=True, **kw)
    return (pattern, *pattern.fill(a.data, b.data))


def _strip_bound(a, b):
    pairs = pack(a, b, block_k=BK, bn=BN).pairs
    tiled = tiled_csr_from_host(b, block_k=BK, bn=BN)
    nblocks = (a.nrows + BR - 1) // BR
    ub = symbolic_strip_nnz(pairs, tile_col_occupancy(tiled),
                            nblocks=nblocks, nnb=tiled.nnb)
    ref = symbolic_strip_nnz_reference(pairs, tile_col_occupancy(tiled),
                                       nblocks=nblocks, nnb=tiled.nnb)
    return ub, ref, nblocks


# ---------------------------------------------------------------------------
# symbolic phase: per-strip upper bound
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(8, 72), st.floats(0.0, 0.3), st.integers(0, 10_000))
def test_strip_bound_vectorized_matches_reference(n, density, seed):
    a = int_host(n, n, density, seed)
    ub, ref, _ = _strip_bound(a, a)
    np.testing.assert_array_equal(ub, ref)


def _assert_dominates(a, b):
    ub, _, nblocks = _strip_bound(a, b)
    exact = symbolic_row_nnz(a, b)
    for r in range(a.nrows):
        assert exact[r] <= ub[r // BR], (
            f"row {r}: exact {exact[r]} > strip bound {ub[r // BR]}")


@settings(max_examples=15, deadline=None)
@given(st.integers(8, 64), st.floats(0.01, 0.35), st.integers(0, 10_000))
def test_strip_bound_dominates_exact_random(n, density, seed):
    a = int_host(n, n, density, seed)
    _assert_dominates(a, a)


def test_strip_bound_dominates_ragged_and_empty_rows():
    # ragged: nnz-per-row varies 0..n; several fully-empty rows; a
    # non-multiple-of-block_r row count exercises the tail strip
    rng = np.random.default_rng(3)
    n = 43
    dense = np.zeros((n, n), np.float32)
    for r in range(n):
        k = int(rng.integers(0, n)) if r % 5 else 0    # every 5th row empty
        cols = rng.choice(n, size=k, replace=False)
        dense[r, cols] = rng.integers(1, 4, k)
    a = HostCSR.from_dense(dense)
    _assert_dominates(a, a)
    ub, _, _ = _strip_bound(a, a)
    assert (ub >= 0).all()


def test_strip_bound_dominates_hub():
    # hub row: one row touching every column (the hub/kron regime the
    # output-accumulation cost lives in)
    n = 40
    dense = (np.random.default_rng(4).random((n, n)) < 0.05).astype(
        np.float32)
    dense[0, :] = 1.0
    dense[:, 0] = 1.0
    a = HostCSR.from_dense(dense)
    _assert_dominates(a, a)


def test_strip_bound_tight_for_disjoint_column_rows():
    # B block-diagonal with dense (BK, BK) blocks: each k-tile's occupied
    # lanes are exactly its block's columns, and different tiles hit
    # disjoint column ranges. All rows of an A strip touch the same
    # k-tiles, so the strip union adds nothing beyond any single row —
    # the bound must equal the exact per-row nnz(C), not just dominate.
    ntiles = 3
    n = ntiles * BK
    bdense = np.zeros((n, n), np.float32)
    for t in range(ntiles):
        bdense[t * BK:(t + 1) * BK, t * BK:(t + 1) * BK] = 1.0
    b = HostCSR.from_dense(bdense)
    adense = np.zeros((n, n), np.float32)
    for blk in range((n + BR - 1) // BR):
        t = blk % ntiles                    # whole strip touches one tile
        adense[blk * BR:(blk + 1) * BR, t * BK] = 1.0
    a = HostCSR.from_dense(adense)
    ub, _, _ = _strip_bound(a, b)
    exact = symbolic_row_nnz(a, b)
    for r in range(n):
        assert ub[r // BR] == exact[r] == BK


# ---------------------------------------------------------------------------
# numeric phase: CompactedC round trip, both variants
# ---------------------------------------------------------------------------


@pytest.mark.pallas
@pytest.mark.parametrize("double_buffer", [False, True])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.25])
def test_sparse_c_kernel_bit_identical(double_buffer, density):
    a = int_host(72, 72, density, seed=int(density * 100) + 7)
    pattern, values, tiled = _pack(a, a)
    kernel = (cluster_spgemm_pairs_sparse_db if double_buffer
              else cluster_spgemm_pairs_sparse)
    cc = ops._sparse_c_kernel(dataclasses.replace(pattern, kernel=kernel),
                              values, tiled)
    got = compacted_c_to_host(cc).to_dense()
    np.testing.assert_array_equal(got, spgemm_reference(a, a))


@pytest.mark.pallas
@pytest.mark.parametrize("double_buffer", [False, True])
@pytest.mark.parametrize("g", [16, 32])
def test_sparse_c_kernel_at_a_selected_height(g, double_buffer):
    """A pattern whose rows come in groups of ``g`` packs its sparse-C
    stream at ``(g, bn)`` windows; the kernel's slabs are float64 scipy's
    product, bit for bit on integer values."""
    a, b = grouped(256, g, BK, g, integer=True), int_host(256, 48, 0.3, g)
    pattern = pack(a, b, block_k=BK, bn=BN, block_r=None, sparse_out=True)
    assert pattern.route == "sparse_c" and pattern.a.block_r == g
    values, tiled = pattern.fill(a.data, b.data)
    kernel = (cluster_spgemm_pairs_sparse_db if double_buffer
              else cluster_spgemm_pairs_sparse)
    cc = ops._sparse_c_kernel(dataclasses.replace(pattern, kernel=kernel),
                              values, tiled)
    assert cc.block_r == g
    np.testing.assert_array_equal(compacted_c_to_host(cc).to_dense(),
                                  scipy_product(a, b))


@pytest.mark.pallas
def test_sparse_c_xla_epilogue_bit_identical_to_kernel():
    a = int_host(64, 64, 0.08, seed=11)
    pattern, values, tiled = _pack(a, a)
    kern = ops._sparse_c_kernel(pattern, values, tiled)
    xla = ops._sparse_c_xla(pattern, values, tiled)
    np.testing.assert_array_equal(np.asarray(kern.table),
                                  np.asarray(xla.table))
    np.testing.assert_array_equal(np.asarray(kern.slabs),
                                  np.asarray(xla.slabs))
    np.testing.assert_array_equal(compacted_c_to_host(kern).to_dense(),
                                  spgemm_reference(a, a))


@pytest.mark.pallas
def test_compacted_c_table_and_counters():
    a = int_host(48, 48, 0.06, seed=5)
    pattern, values, tiled = _pack(a, a)
    pairs = pattern.pairs
    nblocks = (a.nrows + BR - 1) // BR
    table, nlive = compacted_c_table(pairs, nblocks=nblocks, nnb=tiled.nnb)
    assert table.shape == (nblocks * tiled.nnb,)
    assert int((np.asarray(table) > 0).sum()) == nlive
    cc = pattern.run_sparse(values, tiled)
    cnt = compacted_c_counters(cc)
    assert set(cnt) <= set(COUNTER_UNITS)        # all declared with units
    assert cnt["c_bytes_sparse"] <= cnt["c_bytes_dense"]
    assert cnt["c_compaction_steps"] == cc.nslabs_live
    # the compacted bytes scale with live windows, the dense with the
    # full lattice — their ratio is exactly the predicted window density
    dens = ops.predict_c_window_density(pairs, nblocks=nblocks,
                                        nnb=tiled.nnb)
    assert cnt["c_bytes_sparse"] / cnt["c_bytes_dense"] == pytest.approx(
        dens)


def test_compacted_c_from_dense_roundtrip():
    rng = np.random.default_rng(9)
    dense = (rng.random((20, 30)) < 0.2) * rng.integers(1, 9, (20, 30))
    dense = dense.astype(np.float32)
    nblocks, nnb = (20 + BR - 1) // BR, (30 + BN - 1) // BN
    lat = np.zeros((nblocks * BR, nnb * BN), np.float32)
    lat[:20, :30] = dense
    table = np.zeros(nblocks * nnb, np.int32)
    live = 0
    for w in range(nblocks * nnb):
        blk, j = divmod(w, nnb)
        if lat[blk * BR:(blk + 1) * BR, j * BN:(j + 1) * BN].any():
            live += 1
            table[w] = live
    cc = compacted_c_from_dense(lat, table, nrows=20, ncols=30,
                                block_r=BR, bn=BN)
    np.testing.assert_array_equal(np.asarray(cc.to_dense()), dense)
    np.testing.assert_array_equal(compacted_c_to_host(cc).to_dense(), dense)


# ---------------------------------------------------------------------------
# pack routing: output density
# ---------------------------------------------------------------------------


@pytest.mark.pallas
def test_auto_select_routes_by_window_density():
    # sparse output → density under the threshold → the sparse-C tier
    # runs; forced dense must agree bit for bit either way
    a = int_host(80, 80, 0.03, seed=21)

    def product(**threshold):
        pattern = pack(a, a, block_k=BK, bn=BN, **threshold)
        return pattern, np.asarray(pattern.run(*pattern.fill(a.data,
                                                              a.data)))
    pattern, auto = product()
    nblocks = (a.nrows + BR - 1) // BR
    dens = ops.predict_c_window_density(pattern.pairs, nblocks=nblocks,
                                        nnb=(a.ncols + BN - 1) // BN)
    assert 0.0 <= dens <= 1.0
    assert pattern.route == ("sparse_c" if dens <= ops._SPARSE_C_DENSITY
                             else "resident")
    dense_route, forced_dense = product(_SPARSE_C_DENSITY=-1.0)
    sparse_route, forced_sparse = product(_SPARSE_C_DENSITY=1.0)
    assert dense_route.route == "resident"
    assert sparse_route.route == "sparse_c"
    np.testing.assert_array_equal(auto, forced_dense)
    np.testing.assert_array_equal(auto, forced_sparse)
    np.testing.assert_array_equal(auto, spgemm_reference(a, a))


# ---------------------------------------------------------------------------
# workload="chain": planner + serving
# ---------------------------------------------------------------------------


def _a3_ref(a):
    d = a.to_dense()
    return d @ d @ d


def test_chain_a3_end_to_end_with_cache_hits():
    from repro.planner.service import Planner
    a = int_host(64, 64, 0.05, seed=31)
    p = Planner()
    c, plans = p.execute_chain(a, hops=2)
    assert len(plans) == 2
    assert all(pl.workload == "chain" for pl in plans)
    np.testing.assert_array_equal(c.to_dense(), _a3_ref(a))
    # second chain: every hop re-fingerprints the same intermediates →
    # plan-cache hit at every hop (the acceptance criterion)
    hits0 = p.cache.stats["hits"]
    c2, plans2 = p.execute_chain(a, hops=2)
    assert p.cache.stats["hits"] >= hits0 + 2
    assert all(pl.from_cache for pl in plans2)
    np.testing.assert_array_equal(c2.to_dense(), _a3_ref(a))


def test_chain_workload_accepted_and_cached_separately():
    from repro.planner.service import Planner
    a = int_host(40, 40, 0.1, seed=33)
    p = Planner()
    pl_chain = p.plan(a, reuse_hint=5, workload="chain")
    pl_a2 = p.plan(a, reuse_hint=5, workload="a2")
    assert pl_chain.workload == "chain" and pl_a2.workload == "a2"
    with pytest.raises(ValueError):
        p.plan(a, workload="nope")


@pytest.mark.pallas
def test_chain_sparse_hop_forced_pallas_bit_identical():
    # the planner's heuristic never picks pallas off-TPU — force the
    # sparse-C hop by shipping the plan a TPU backend would (the
    # test_spgemm_pallas idiom), covering the perm-undo of both hop
    # shapes (symmetric A·A, rows-only C·A)
    from repro.planner.service import Planner, _materialize
    from repro.planner.cost_model import Candidate
    from repro.planner.plan_cache import Plan
    from repro.planner.features import fingerprint
    a = int_host(72, 72, 0.05, seed=41)
    ref = a.to_dense()
    p = Planner()
    perm, bounds, mc, _ = _materialize(a, Candidate("rcm", "pallas"))
    plan1 = Plan(fingerprint=fingerprint(a), reorder="rcm", scheme="pallas",
                 reuse_hint=50, max_cluster=mc, perm=perm,
                 boundaries=bounds, workload="chain")
    h1 = p._chain_hop(plan1, a, None)                  # A·A, symmetric perm
    np.testing.assert_array_equal(h1.to_dense(), ref @ ref)
    perm2 = _materialize(h1, Candidate("rcm", "pallas"))[0]
    plan2 = Plan(fingerprint=fingerprint(h1), reorder="rcm",
                 scheme="pallas", reuse_hint=50, max_cluster=mc,
                 perm=perm2, workload="chain")
    h2 = p._chain_hop(plan2, h1, a)                    # C·A, rows-only perm
    np.testing.assert_array_equal(h2.to_dense(), ref @ ref @ ref)
    # second pass hits the exec cache (packed operands, sparse stream)
    assert any(v[0] == "chain" for v in p._exec_cache.values())
    h1b = p._chain_hop(plan1, a, None)
    np.testing.assert_array_equal(h1b.to_dense(), ref @ ref)


@pytest.mark.pallas
@pytest.mark.parametrize("g", [16, 32])
def test_chain_two_hops_at_selected_heights(g, monkeypatch):
    """``A·(A·M)`` on Pallas hops, both through the sparse-C tier: hop 1
    packs at height ``g``, hop 2 at the height its intermediate selects,
    the intermediate stays on the device (``_DeviceCSR``) until the
    carry, and the product is float64 scipy's, bit for bit."""
    from repro.planner import service
    from repro.planner.cost_model import Candidate
    from repro.planner.plan_cache import PlanCache
    from repro.planner.service import Planner
    monkeypatch.setattr(service, "_CHAIN_DENSE_C_BUDGET", 0)
    a = grouped(2048, g, 128, g, integer=True)
    m = merge(2048, 128, g)
    p = Planner(cache=PlanCache(),
                candidates=(Candidate("original", "pallas"),))
    carried, to_host = [], service._DeviceCSR.to_host
    monkeypatch.setattr(service._DeviceCSR, "to_host", lambda self: (
        carried.append(self.shape) or to_host(self)))
    c, plans = p.execute_chain(a, (a, m))
    assert [pl.scheme for pl in plans] == ["pallas", "pallas"]
    heights = [v[1].a.block_r for v in p._exec_cache.values()
               if v[0] == "chain"]
    assert len(heights) == 2 and heights[0] == g and min(heights) > 8
    assert carried == [(2048, 1024), (2048, 1024)]
    np.testing.assert_array_equal(c.to_dense(), scipy_product(a, a, m))


def test_engine_chain_requests():
    from repro.planner.service import Planner
    from repro.serve.engine import SpGEMMServer
    a = int_host(48, 48, 0.08, seed=51)
    srv = SpGEMMServer(Planner())
    r1 = srv.submit(a, hops=2)
    assert r1.workload == "chain" and isinstance(r1.result, HostCSR)
    np.testing.assert_array_equal(r1.result.to_dense(), _a3_ref(a))
    assert not r1.plan_cache_hit
    r2 = srv.submit(a, hops=2)
    assert r2.plan_cache_hit          # every hop from cache on the rerun
    np.testing.assert_array_equal(r2.result.to_dense(), _a3_ref(a))
    with pytest.raises(ValueError):
        srv.submit(a, b=a, hops=2)    # chain requests take b=None


def test_chain_counters_registered():
    for key in ("c_nnz", "c_bytes_dense", "c_bytes_sparse",
                "c_compaction_steps"):
        assert key in COUNTER_UNITS
