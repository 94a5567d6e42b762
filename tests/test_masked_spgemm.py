"""Masked products ``submit(a, Masked(b, mask))``: ``(a · b) ∘ mask`` on
exactly the mask's pattern, served through ``AsyncSpGEMMServer``.

Compared with the plain reference ``kernels/ref.py::masked_spgemm_ref``
on seeded random operands: the square and a·b, plans with and without a
permutation, a mask with empty rows, a mask with entries where the
product has no term (explicit zeros), a mask that removes every pair of
some C windows, and a plan off the Pallas scheme. Also: a new value set
refills without a pack; a different mask takes an exec entry and a
result of its own; and validation rejects a malformed mask.
"""
import numpy as np
import pytest

from repro.core.formats import HostCSR, Masked
from repro.kernels import ops
from repro.kernels.ref import masked_spgemm_ref
from repro.obs import metrics as obs_metrics
from repro.planner import Candidate, Planner
from repro.planner.features import fingerprint
from repro.planner.plan_cache import Plan, PlanCache
from repro.resilience.errors import InvalidOperandError
from repro.resilience.policy import ResiliencePolicy
from repro.serve.engine import SpGEMMServer
from repro.serve.frontend import AsyncSpGEMMServer

HINT = 20


def _rand(n, m, density, seed, *, lower=False) -> HostCSR:
    rng = np.random.default_rng(seed)
    d = (rng.random((n, m)) < density) * rng.uniform(0.5, 1.5, (n, m))
    if lower:
        d = np.tril(d + d.T) + np.eye(n)
    return HostCSR.from_dense(d.astype(np.float32))


def _revalued(h: HostCSR, seed: int) -> HostCSR:
    data = np.random.default_rng(seed).uniform(0.5, 1.5, h.nnz)
    return HostCSR(h.indptr, h.indices, data.astype(np.float32), h.shape)


def _server(a: HostCSR, *, scheme="pallas", perm=None, workers=1):
    planner = Planner(cache=PlanCache(), resilience=ResiliencePolicy(),
                      candidates=(Candidate("original", scheme),))
    planner.cache.put(Plan(fingerprint=fingerprint(a),
                           reorder="original" if perm is None else "rcm",
                           scheme=scheme, reuse_hint=HINT, workload="a2",
                           perm=perm))
    return AsyncSpGEMMServer(SpGEMMServer(planner=planner),
                             workers=workers)


def _check(resp, a, b, mask):
    got = resp.result
    assert isinstance(got, HostCSR) and got.shape == mask.shape
    np.testing.assert_array_equal(got.indptr, mask.indptr)
    np.testing.assert_array_equal(got.indices, mask.indices)
    want = masked_spgemm_ref(a, b, mask)
    np.testing.assert_allclose(got.to_dense(), want, rtol=2e-6, atol=1e-6)
    assert resp.workload == "masked" and not resp.degraded


def _serve(a, b, mask, **kw):
    srv = _server(a, **kw)
    try:
        return srv.submit_wait(a, Masked(b, mask), reuse_hint=HINT), srv
    finally:
        srv.close()


def _case(name):
    """(a, b, mask) of one masked product."""
    if name == "square":
        a = _rand(300, 300, 0.03, 1, lower=True)
        return a, None, a
    if name == "ab":
        return (_rand(200, 300, 0.04, 2), _rand(300, 260, 0.04, 3),
                _rand(200, 260, 0.1, 4))
    if name == "empty_rows":
        a = _rand(260, 260, 0.04, 5)
        m = _rand(260, 260, 0.1, 6).to_dense()
        m[17:90] = 0.0
        m[200:] = 0.0
        return a, None, HostCSR.from_dense(m)
    if name == "no_term":
        # a block-diagonal A: A·A has no term off its two blocks, where
        # the mask still asks for entries
        d = _rand(256, 256, 0.1, 7).to_dense()
        d[:128, 128:] = 0.0
        d[128:, :128] = 0.0
        a = HostCSR.from_dense(d)
        m = _rand(256, 256, 0.05, 8)
        return a, None, m
    if name == "pruned_windows":
        # the mask reads only the first column tile: every pair of the
        # other two tiles' windows is dropped
        a = _rand(300, 300, 0.05, 9)
        m = _rand(300, 300, 0.2, 10).to_dense()
        m[:, 128:] = 0.0
        return a, None, HostCSR.from_dense(m)
    raise KeyError(name)


@pytest.mark.parametrize("name", ["square", "ab", "empty_rows", "no_term",
                                  "pruned_windows"])
def test_masked_product_equals_the_reference(name):
    a, b, mask = _case(name)
    resp, srv = _serve(a, b, mask)
    _check(resp, a, b, mask)
    assert resp.kernel_path == "pallas" and resp.plan_cache_hit
    entry, = srv.server.planner._exec_cache.values()
    assert entry[0] == "masked" and entry[1].route == "sparse_c"


@pytest.mark.parametrize("name", ["square", "ab"])
def test_a_plan_permutation_applies_to_the_mask(name):
    """Symmetric when squared, rows only otherwise; the answer comes back
    in the order sent."""
    a, b, mask = _case(name)
    perm = np.random.default_rng(11).permutation(a.nrows)
    resp, _ = _serve(a, b, mask, perm=perm)
    _check(resp, a, b, mask)


def test_no_term_entries_are_explicit_zeros():
    a, b, mask = _case("no_term")
    resp, _ = _serve(a, b, mask)
    rows = np.repeat(np.arange(mask.nrows), mask.row_nnz())
    off_block = (rows < 128) != (mask.indices < 128)
    assert off_block.any()
    assert np.all(resp.result.data[off_block] == 0.0)


def test_the_mask_prunes_pairs_and_windows():
    """The pruned stream is shorter than the unmasked one at the same
    height, and the windows the mask never reads get no slab."""
    a, _, mask = _case("pruned_windows")
    reg = obs_metrics.get_registry()
    reg.reset()
    resp, srv = _serve(a, None, mask)
    _check(resp, a, None, mask)
    pattern = next(iter(srv.server.planner._exec_cache.values()))[1]
    kept, unmasked = pattern.mask_pairs
    assert kept < unmasked
    snap = reg.snapshot()
    assert snap[reg._key("sxs_mask_pairs", {"pairs": "kept"})] == kept
    assert snap[reg._key("sxs_mask_pairs", {"pairs": "pruned"})] \
        == unmasked - kept
    table = np.asarray(pattern.sparse_pairs[3]).reshape(-1, 3)
    assert not table[:, 1:].any() and table[:, 0].all()


def test_a_plan_off_the_pallas_scheme_reads_the_dense_product():
    a, b, mask = _case("ab")
    resp, _ = _serve(a, b, mask, scheme="rowwise")
    _check(resp, a, b, mask)
    assert resp.kernel_path == "xla"


@pytest.mark.parametrize("scheme", ["rowwise", "pallas"])
def test_a_dense_fallback_over_its_budget_is_refused(monkeypatch, scheme):
    """Off the sparse-C tier, a masked product is read from the dense
    product only where that C fits its budget: a wider one is refused,
    and a failing sparse launch raises its own error even with the
    ladder armed, rather than build the dense C."""
    from repro.planner import service
    a, b, mask = _case("ab")
    srv = _server(a, scheme=scheme)
    srv.close()
    planner = srv.server.planner
    plan = planner.cache.get(fingerprint(a), HINT)
    monkeypatch.setattr(service, "_MASKED_DENSE_C_BUDGET",
                        4 * mask.nrows * mask.ncols - 1)
    monkeypatch.setattr(planner, "execute", lambda *_: pytest.fail(
        "the dense product was built"))
    if scheme == "rowwise":
        with pytest.raises(ValueError, match="budget"):
            planner.execute_masked(plan, a, b, mask)
        return

    def broken(*_args, **_kw):
        raise RuntimeError("sparse launch failed")
    monkeypatch.setattr(planner, "_masked_sparse", broken)
    assert planner.resilience.ladder
    with pytest.raises(RuntimeError, match="sparse launch failed"):
        planner.execute_masked(plan, a, b, mask)


def test_new_values_refill_without_a_pack():
    """Same patterns, new values of a and of the mask: one refill, no
    pack, the same exec entry; the mask's values equal to a's upload
    once."""
    a, _, _ = _case("square")
    reg = obs_metrics.get_registry()
    srv = _server(a)
    try:
        srv.submit_wait(a, Masked(None, a), reuse_hint=HINT)
        reg.reset()
        a2 = _revalued(a, 12)
        resp = srv.submit_wait(a2, Masked(None, a2), reuse_hint=HINT)
        snap = reg.snapshot()
        assert snap.get(reg._key("exec_cache_packs", {}), 0) == 0
        assert snap[reg._key("exec_cache_refills", {})] == 1
        _check(resp, a2, None, a2)
        entry, = srv.server.planner._exec_cache.values()
        assert entry[2][3].shape == (a.nnz,)
        # the mask's own values on a's pattern: a refill of the same entry
        mask2 = _revalued(a, 13)
        resp = srv.submit_wait(a2, Masked(None, mask2), reuse_hint=HINT)
        _check(resp, a2, None, mask2)
        snap = reg.snapshot()
        assert snap.get(reg._key("exec_cache_packs", {}), 0) == 0
        assert snap[reg._key("exec_cache_refills", {})] == 2
        assert len(srv.server.planner._exec_cache) == 1
    finally:
        srv.close()


def test_a_different_mask_shares_no_entry_and_no_result():
    """Two requests on the same operands that differ only in the mask
    coalesce into neither one exec entry nor one answer."""
    a, _, mask = _case("empty_rows")
    other = _rand(260, 260, 0.1, 14)
    srv = _server(a, workers=0)
    try:
        t1 = srv.submit(a, Masked(None, mask), reuse_hint=HINT)
        t2 = srv.submit(a, Masked(None, other), reuse_hint=HINT)
        t3 = srv.submit(a, Masked(None, mask), reuse_hint=HINT)
        srv.pump()
        r1, r2, r3 = (t.result(60) for t in (t1, t2, t3))
    finally:
        srv.close()
    assert not r1.coalesced and not r2.coalesced and r3.coalesced
    assert r3.result is r1.result
    _check(r1, a, None, mask)
    _check(r2, a, None, other)
    assert len(srv.server.planner._exec_cache) == 2


@pytest.mark.parametrize("bad, field", [
    (HostCSR.from_dense(np.ones((10, 9), np.float32)), "shape"),
    (HostCSR(np.array([0, 2] + [2] * 9), np.array([3, 1], np.int32),
             np.ones(2, np.float32), (10, 10)), "indices"),
])
def test_validation_rejects_a_malformed_mask(bad, field):
    a = _rand(10, 10, 0.3, 15)
    with pytest.raises(InvalidOperandError) as e:
        SpGEMMServer().submit(a, Masked(None, bad))
    assert e.value.field == field


def test_validation_rejects_a_masked_square_of_a_rectangle():
    a = _rand(10, 12, 0.3, 16)
    with pytest.raises(InvalidOperandError) as e:
        SpGEMMServer().submit(a, Masked(None, _rand(10, 12, 0.3, 17)))
    assert e.value.field == "shape"


def test_a_mask_that_is_a_is_scanned_once(monkeypatch):
    from repro.resilience import validation
    seen = []
    orig = validation.validate_host_csr
    monkeypatch.setattr(validation, "validate_host_csr",
                        lambda h, name="operand": (seen.append(name),
                                                   orig(h, name)))
    a = _rand(10, 10, 0.3, 18)
    validation.validate_request_pair(a, Masked(None, a))
    assert seen == ["a"]


def test_mask_positions_point_into_the_slabs():
    """The pack's ``mask_pos`` reads each mask entry's value out of the
    flattened sparse-C slabs of the product."""
    a, _, mask = _case("pruned_windows")
    from repro.core.formats import select_block_k
    pattern = ops.pack_spgemm_pattern(a, a, block_k=select_block_k(a),
                                      mask=mask)
    cc = pattern.run_sparse(*pattern.fill(a.data))
    got = np.asarray(cc.slabs).reshape(-1)[np.asarray(pattern.mask_pos)]
    want = masked_spgemm_ref(a, None, mask) / np.where(
        mask.to_dense() == 0, 1.0, mask.to_dense())
    rows = np.repeat(np.arange(mask.nrows), mask.row_nnz())
    np.testing.assert_allclose(got, want[rows, mask.indices], rtol=2e-6,
                               atol=1e-6)
