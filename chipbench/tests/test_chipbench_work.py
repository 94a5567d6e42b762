"""The yardstick: flops and compulsory bytes against scipy, the peaks
table, and the Kronecker generator against the program's."""
import numpy as np
import pytest
import scipy.sparse as sp

from chipbench import work
from chipbench.graphs import kronecker_pattern


def _random_csr(n, m, density, seed):
    a = sp.random(n, m, density=density, format="csr",
                  random_state=np.random.default_rng(seed))
    a.sort_indices()
    return a


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spgemm_flops_count_every_product_of_two_nonzeros(seed):
    a = _random_csr(40, 30, 0.15, seed)
    b = _random_csr(30, 50, 0.2, seed + 10)
    # each product a_ik * b_kj is one multiply and one add
    ones_a = a.copy()
    ones_a.data[:] = 1
    ones_b = b.copy()
    ones_b.data[:] = 1
    products = (ones_a @ ones_b).sum()
    assert work.spgemm_flops(a.indices, b.indptr) == 2 * products
    flops, nbytes = work.spgemm_work(a.indptr, a.indices, b.indptr, b.nnz,
                                     (a @ b).nnz)
    assert flops == 2 * products
    assert nbytes == (4 * 41 + 8 * a.nnz) + (4 * 31 + 8 * b.nnz) \
        + (4 * 41 + 8 * (a @ b).nnz)


def test_spmm_work():
    a = _random_csr(64, 64, 0.1, 3)
    flops, nbytes = work.spmm_work(64, 64, a.nnz, 16)
    assert flops == 2 * a.nnz * 16
    assert nbytes == 4 * 65 + 8 * a.nnz + 2 * 4 * 64 * 16


def test_peaks_of_the_v5e_and_unknown_kinds_raise():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert "source" in p
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_least_time_takes_the_longer_bound():
    p = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.least_time_s(1000, 50, p) == (10.0, "compute")
    assert work.least_time_s(10, 500, p) == (50.0, "memory")


@pytest.mark.parametrize("scale", [6, 9])
def test_kronecker_pattern_is_the_programs_generator(scale):
    """The program's graph with its vertices relabelled at random, as
    the Graph500 specification requires: the labels are the generator's
    next draw after the edges."""
    from repro.core.suite import gen_kron
    indptr, indices = kronecker_pattern(scale, 16, 7)
    g = gen_kron(scale, 16, 7)
    n = 1 << scale
    rng = np.random.default_rng(7)
    for _ in range(2 * scale):
        rng.random(16 * n)
    label = rng.permutation(n)
    p = sp.csr_matrix((np.ones(n), (label, np.arange(n))), shape=(n, n))
    want = (p @ sp.csr_matrix((np.ones(g.nnz), g.indices, g.indptr),
                              shape=(n, n)) @ p.T).tocsr()
    want.sort_indices()
    assert np.array_equal(indptr, want.indptr)
    assert np.array_equal(indices, want.indices)
    assert not np.array_equal(indices, g.indices)
    a = sp.csr_matrix((np.ones(len(indices)), indices, indptr), shape=(n, n))
    assert (a != a.T).nnz == 0 and a.diagonal().min() == 1
