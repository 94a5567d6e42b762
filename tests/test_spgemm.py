"""SpGEMM correctness: row-wise and cluster-wise vs the dense oracle,
including invariance under reordering + clustering (the paper's pipelines)."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # pragma: no cover - container without hypothesis
    from _hypo_shim import given, settings, st

from repro.core.clustering import (fixed_length_clusters,
                                   hierarchical_clusters,
                                   variable_length_clusters)
from repro.core.formats import HostCSR, csr_cluster_from_host, csr_from_host
from repro.core.reorder import reorder
from repro.core.spgemm import (flops_spgemm, length_bins,
                               spgemm_clusterwise_dense,
                               spgemm_clusterwise_dense_binned,
                               spgemm_reference, spgemm_rowwise_dense,
                               spgemm_rowwise_dense_binned,
                               spmm_clusterwise, spmm_rowwise, symbolic_nnz)


def rand_host(n, m, density, seed):
    rng = np.random.default_rng(seed)
    dense = (rng.random((n, m)) < density) * rng.uniform(
        0.5, 2.0, (n, m)).astype(np.float32)
    return HostCSR.from_dense(dense.astype(np.float32))


def max_row(h: HostCSR) -> int:
    return max(1, int(h.row_nnz().max()))


def test_rowwise_matches_oracle():
    a = rand_host(24, 20, 0.25, 0)
    b = rand_host(20, 28, 0.25, 1)
    got = np.asarray(spgemm_rowwise_dense(csr_from_host(a), csr_from_host(b),
                                          max_row_b=max_row(b)))
    np.testing.assert_allclose(got, spgemm_reference(a, b), rtol=1e-5,
                               atol=1e-6)


def test_clusterwise_matches_oracle_fixed():
    a = rand_host(24, 20, 0.3, 2)
    b = rand_host(20, 24, 0.3, 3)
    cl = fixed_length_clusters(a, 4)
    cc = csr_cluster_from_host(a, cl.boundaries.tolist(), max_cluster=4)
    got = np.asarray(spgemm_clusterwise_dense(cc, csr_from_host(b),
                                              max_row_b=max_row(b)))
    np.testing.assert_allclose(got, spgemm_reference(a, b), rtol=1e-5,
                               atol=1e-6)


def test_clusterwise_matches_oracle_variable():
    a = rand_host(30, 30, 0.2, 4)
    cl = variable_length_clusters(a)
    cc = csr_cluster_from_host(a, cl.boundaries.tolist(),
                               max_cluster=cl.max_cluster)
    got = np.asarray(spgemm_clusterwise_dense(cc, csr_from_host(a),
                                              max_row_b=max_row(a)))
    np.testing.assert_allclose(got, spgemm_reference(a, a), rtol=1e-5,
                               atol=1e-6)


def test_a_squared_reorder_invariance():
    """(PAPᵀ)² == P A² Pᵀ — reordering must not change the math."""
    a = rand_host(32, 32, 0.15, 5)
    b, perm = reorder(a, "rcm")
    c_orig = spgemm_reference(a, a)
    c_reord = np.asarray(spgemm_rowwise_dense(
        csr_from_host(b), csr_from_host(b), max_row_b=max_row(b)))
    np.testing.assert_allclose(c_reord, c_orig[np.ix_(perm, perm)],
                               rtol=1e-5, atol=1e-6)


def test_hierarchical_pipeline_end_to_end():
    """Full Alg. 3 pipeline: cluster -> reorder -> CSR_Cluster -> SpGEMM."""
    a = rand_host(40, 40, 0.15, 6)
    cl = hierarchical_clusters(a)
    ar = a.permute_symmetric(cl.perm)
    cc = csr_cluster_from_host(ar, cl.boundaries.tolist(),
                               max_cluster=cl.max_cluster)
    got = np.asarray(spgemm_clusterwise_dense(cc, csr_from_host(ar),
                                              max_row_b=max_row(ar)))
    want = spgemm_reference(a, a)[np.ix_(cl.perm, cl.perm)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_spmm_rowwise_and_clusterwise_tall_skinny():
    a = rand_host(32, 24, 0.2, 7)
    rng = np.random.default_rng(8)
    bdense = rng.normal(size=(24, 8)).astype(np.float32)
    want = a.to_dense() @ bdense
    got_row = np.asarray(spmm_rowwise(csr_from_host(a), bdense))
    np.testing.assert_allclose(got_row, want, rtol=1e-4, atol=1e-5)
    cl = variable_length_clusters(a)
    cc = csr_cluster_from_host(a, cl.boundaries.tolist(),
                               max_cluster=cl.max_cluster)
    got_cl = np.asarray(spmm_clusterwise(cc, bdense))
    np.testing.assert_allclose(got_cl, want, rtol=1e-4, atol=1e-5)


def test_rowwise_binned_matches_oracle():
    """Skewed B (one hub row) — binned passes must equal the oracle."""
    rng = np.random.default_rng(10)
    dense = (rng.random((40, 40)) < 0.1).astype(np.float32)
    dense[:, 3] = 1.0                       # hub column -> one 40-nnz B row
    a = HostCSR.from_dense(dense)
    dev = csr_from_host(a)
    bins = length_bins(a.row_nnz()[a.indices], pad_sentinel=dev.nnz_cap)
    assert len(bins) > 1                    # the skew actually splits bins
    got = np.asarray(spgemm_rowwise_dense_binned(dev, dev, bins))
    np.testing.assert_allclose(got, spgemm_reference(a, a), rtol=1e-5,
                               atol=1e-5)


def test_clusterwise_binned_matches_oracle():
    rng = np.random.default_rng(11)
    dense = (rng.random((32, 32)) < 0.15).astype(np.float32)
    dense[:, 5] = 1.0
    a = HostCSR.from_dense(dense)
    cl = fixed_length_clusters(a, 4)
    cc = csr_cluster_from_host(a, cl.boundaries.tolist(), max_cluster=4)
    dev_b = csr_from_host(a)
    total = int(np.asarray(cc.cluster_ptr)[-1])
    slot_cols = np.asarray(cc.cols)[:total].astype(np.int64)
    lens = np.where(slot_cols < a.ncols,
                    a.row_nnz()[np.clip(slot_cols, 0, a.nrows - 1)], 0)
    bins = length_bins(lens, pad_sentinel=cc.slot_cap)
    got = np.asarray(spgemm_clusterwise_dense_binned(cc, dev_b, bins))
    np.testing.assert_allclose(got, spgemm_reference(a, a), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("max_updates", [64, 1 << 20])
def test_clusterwise_bins_split_at_update_bound(monkeypatch, max_updates):
    """Buckets over the per-pass update bound split into several bins of
    one width, each within the bound, covering the same slots in order —
    and the product is unchanged."""
    rng = np.random.default_rng(12)
    dense = (rng.random((48, 48)) < 0.2).astype(np.float32)
    dense[:, 5] = 1.0
    a = HostCSR.from_dense(dense)
    cl = fixed_length_clusters(a, 4)
    cc = csr_cluster_from_host(a, cl.boundaries.tolist(), max_cluster=4)
    total = int(np.asarray(cc.cluster_ptr)[-1])
    slot_cols = np.asarray(cc.cols)[:total].astype(np.int64)
    lens = np.where(slot_cols < a.ncols,
                    a.row_nnz()[np.clip(slot_cols, 0, a.nrows - 1)], 0)
    whole = length_bins(lens, pad_sentinel=cc.slot_cap)
    from repro.core import spgemm
    monkeypatch.setattr(spgemm, "MAX_PASS_UPDATES", max_updates)
    bins = length_bins(lens, pad_sentinel=cc.slot_cap)
    assert all(s.size * w <= max(max_updates, 8 * w) for s, w in bins)
    if max_updates == 64:
        assert len(bins) > len(whole)
    live = lambda bs: [x for s, _ in bs for x in s.tolist()
                       if x < cc.slot_cap]
    assert live(bins) == live(whole)
    got = np.asarray(spgemm_clusterwise_dense_binned(cc, csr_from_host(a),
                                                     bins))
    np.testing.assert_allclose(got, spgemm_reference(a, a), rtol=1e-5,
                               atol=1e-5)


def test_flops_and_symbolic():
    a = rand_host(16, 16, 0.3, 9)
    c = spgemm_reference(a, a)
    assert symbolic_nnz(a, a) == int((c != 0).sum())
    # flops = 2 * expanded products >= 2 * nnz(C)
    assert flops_spgemm(a, a) >= 2 * int((c != 0).sum())


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 24), st.floats(0.1, 0.4), st.integers(0, 1000),
       st.sampled_from(["fixed", "variable", "hierarchical"]))
def test_property_clusterwise_equals_rowwise(n, density, seed, scheme):
    a = rand_host(n, n, density, seed)
    if scheme == "fixed":
        cl = fixed_length_clusters(a, 4)
        ar = a
    elif scheme == "variable":
        cl = variable_length_clusters(a)
        ar = a
    else:
        cl = hierarchical_clusters(a)
        ar = a.permute_symmetric(cl.perm)
    cc = csr_cluster_from_host(ar, cl.boundaries.tolist(),
                               max_cluster=cl.max_cluster)
    rw = np.asarray(spgemm_rowwise_dense(csr_from_host(ar), csr_from_host(ar),
                                         max_row_b=max_row(ar)))
    cw = np.asarray(spgemm_clusterwise_dense(cc, csr_from_host(ar),
                                             max_row_b=max_row(ar)))
    np.testing.assert_allclose(cw, rw, rtol=1e-4, atol=1e-5)
