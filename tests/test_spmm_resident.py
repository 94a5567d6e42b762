"""The Pallas SpMM exec entry holds A's compact stream on the device.

The planner packs Â·X's sparse operand once per value set
(``pack_spmm_stream``): the compact stream is built on the host from
A's layout and data, uploaded once, and launched on by every request
that sends the same Â. Covers:
  * the packed stream is byte-identical to the squeeze of a full BCC
    pack (``bcc_from_host`` → ``bcc_compact_stream``), over random
    values, empty row blocks, ragged blocks, a repeated (row, col) entry
    and a wide A;
  * the cached entry holds only that stream on the device, and no
    padded value lattice;
  * requests with one Â and new X's, with and without a row
    permutation, and a new Â value set (a new entry) match a float64
    dense reference;
  * ``exec_cache_hits`` counts every launch on a cached entry that
    neither packed nor refilled: requests − packs, for SpMM and for a
    same-values A·A repeat.
"""
import jax
import numpy as np
import pytest

from repro.core.formats import BCC, HostCSR, bcc_from_host
from repro.kernels import ops
from repro.obs.metrics import get_registry
from repro.planner import Planner
from repro.planner.features import fingerprint
from repro.planner.plan_cache import Plan
from repro.resilience.policy import ResiliencePolicy

pytestmark = pytest.mark.pallas


def _random(n, m, density, seed):
    rng = np.random.default_rng(seed)
    d = (rng.random((n, m)) < density) * rng.uniform(0.5, 1.5, (n, m))
    return HostCSR.from_dense(d.astype(np.float32))


def _revalued(h: HostCSR, seed: int) -> HostCSR:
    data = np.random.default_rng(seed).uniform(0.5, 1.5, h.nnz)
    return HostCSR(h.indptr, h.indices, data.astype(np.float32), h.shape)


def _with_empty_blocks() -> HostCSR:
    """Row blocks 1, 2 and 5 of 8 rows empty: the stream needs cover
    steps for them and tail padding."""
    d = np.zeros((64, 200), np.float32)
    for i in range(64):
        if i // 8 not in (1, 2, 5):
            d[i, (i * 7) % 200] = 1.0 + i / 10
            d[i, 150 + i % 50] = 2.0
    return HostCSR.from_dense(d)


def _ragged() -> HostCSR:
    """Row block 0 spans four column tiles, the others one, and block 3
    is empty: the padded lattice holds four slabs a block, the stream
    about one."""
    d = np.zeros((128, 512), np.float32)
    d[0, [0, 130, 260, 400]] = 1.0
    d[8:128, 5] = np.arange(120) / 7 + 1
    d[24:32] = 0.0
    return HostCSR.from_dense(d)


def _with_repeat() -> HostCSR:
    """Row 2 holds its first column twice, the second time with 7.0."""
    a = _random(40, 40, 0.15, 3)
    rows = np.repeat(np.arange(40), a.row_nnz()).tolist() + [2]
    cols = a.indices.tolist() + [int(a.indices[a.indptr[2]])]
    vals = a.data.tolist() + [7.0]
    order = np.lexsort((np.arange(len(rows)), cols, rows))
    rows, cols, vals = (np.asarray(x)[order] for x in (rows, cols, vals))
    indptr = np.searchsorted(rows, np.arange(41))
    return HostCSR(indptr, cols, vals, (40, 40))


CASES = {
    "random": lambda: _random(64, 64, 0.2, 0),
    "empty_blocks": _with_empty_blocks,
    "ragged": _ragged,
    "repeat": _with_repeat,
    "wide": lambda: _random(24, 520, 0.05, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_packed_stream_is_the_squeeze_of_a_full_pack(case):
    a = CASES[case]()
    shape, stream = ops.pack_spmm_stream(a)
    want = ops.bcc_compact_stream(bcc_from_host(a), cover_all_blocks=True)
    assert shape == (a.nrows, a.ncols, 8, 128)
    assert all(isinstance(s, jax.Array) for s in stream)
    for got, ref in zip(stream, want):
        got = np.asarray(got)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()


def _spmm_plan(a, perm=None):
    return Plan(fingerprint=fingerprint(a),
                reorder="original" if perm is None else "rcm",
                scheme="pallas", reuse_hint=10, workload="spmm", perm=perm)


def _planner():
    return Planner(resilience=ResiliencePolicy.disabled())


def _x(n, seed):
    return np.random.default_rng(seed).standard_normal(
        (n, 16)).astype(np.float32)


def test_cached_entry_holds_only_the_stream_on_the_device():
    a = _ragged()
    planner = _planner()
    planner.execute(_spmm_plan(a), a, _x(a.ncols, 0))
    (entry,) = planner._exec_cache.values()
    assert entry[0] == "spmm_pallas"
    assert not any(isinstance(v, BCC) for v in entry)
    arrays = [v for v in jax.tree_util.tree_leaves(entry)
              if isinstance(v, jax.Array)]
    stream = ops.bcc_compact_stream(bcc_from_host(a), cover_all_blocks=True)
    assert len(arrays) == 3
    assert sum(v.nbytes for v in arrays) == sum(s.nbytes for s in stream)
    # the values are the live slabs: 24 steps against 16 × 4 slots
    lattice = np.asarray(bcc_from_host(a).values).nbytes
    assert arrays[-1].nbytes * 2 < lattice


@pytest.mark.parametrize("permuted", [False, True])
def test_one_a_three_x_and_a_new_a_match_float64(permuted):
    get_registry().reset()
    a = _random(96, 80, 0.1, 4)
    perm = (np.random.default_rng(5).permutation(a.nrows) if permuted
            else None)
    planner = _planner()
    plan = _spmm_plan(a, perm)

    def check(ai, seed):
        x = _x(ai.ncols, seed)
        got = planner.execute(plan, ai, x)
        want = ai.to_dense().astype(np.float64) @ x.astype(np.float64)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    for seed in (1, 2, 3):
        check(a, seed)
    assert len(planner._exec_cache) == 1
    # a new value set of Â is a new entry, packed once
    check(_revalued(a, 9), 4)
    assert len(planner._exec_cache) == 2
    reg = get_registry()
    assert reg.counter("exec_cache_packs").value == 2
    assert reg.counter("exec_cache_hits", kind="spmm_pallas").value == 2


@pytest.mark.parametrize("workload", ["spmm", "a2"])
def test_exec_cache_hits_read_requests_less_packs(workload):
    get_registry().reset()
    a = _random(64, 64, 0.2, 6)
    planner = _planner()
    if workload == "spmm":
        plan, kind = _spmm_plan(a), "spmm_pallas"
    else:
        plan = Plan(fingerprint=fingerprint(a), reorder="original",
                    scheme="pallas", reuse_hint=10)
        kind = "pallas"
    requests = 4
    for seed in range(requests):
        planner.execute(plan, a, _x(a.ncols, seed)
                        if workload == "spmm" else None)
    reg = get_registry()
    packs = reg.counter("exec_cache_packs").value
    assert packs == 1
    assert reg.counter("exec_cache_refills").value == 0
    assert reg.counter("exec_cache_hits", kind=kind).value \
        == requests - packs
