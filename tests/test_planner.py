"""Planner subsystem tests (ISSUE 2).

Covers the four contracted behaviors:
  * fingerprint stability under value perturbation (pattern-keyed cache);
  * plan cache hit/miss accounting + on-disk round-trip;
  * break-even monotonicity in ``reuse_hint``;
  * planner-never-worse-than-identity (by total measured cost) on four
    suite families — the sweep-sized variant is marked ``slow`` and stays
    out of tier-1.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.formats import HostCSR
from repro.core.spgemm import spgemm_reference
from repro.core.suite import (gen_block_diag, gen_caveman, gen_er,
                              gen_mesh2d, gen_powerlaw)
from repro.planner import (Candidate, CostModel, DEFAULT_CANDIDATES,
                           IDENTITY, Plan, PlanCache, Planner, amortizes,
                           break_even_reuse, extract_features, fingerprint,
                           reuse_bucket)


def _scrambled_caveman(n=384, cave=16, seed=0):
    a = gen_caveman(n, cave=cave, seed=seed)
    return a.permute_symmetric(np.random.default_rng(seed).permutation(n))


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------


def test_fingerprint_stable_under_value_perturbation():
    a = _scrambled_caveman()
    rng = np.random.default_rng(3)
    perturbed = HostCSR(a.indptr, a.indices,
                        a.data * (1 + rng.normal(0, 0.5, a.nnz)
                                  ).astype(np.float32), a.shape)
    assert fingerprint(perturbed) == fingerprint(a)


def test_fingerprint_sensitive_to_pattern():
    a = _scrambled_caveman()
    fp = fingerprint(a)
    # drop one nonzero: different pattern, different fingerprint
    b = HostCSR(np.concatenate([a.indptr[:-1], [a.indptr[-1] - 1]]),
                a.indices[:-1], a.data[:-1], a.shape)
    assert fingerprint(b) != fp
    # different shape, same arrays
    c = HostCSR(a.indptr, a.indices, a.data, (a.nrows, a.ncols + 1))
    assert fingerprint(c) != fp


def test_features_are_finite_and_scale_free():
    for gen in (lambda: gen_er(256, avg_deg=8, seed=1),
                lambda: gen_mesh2d(16, seed=2),
                _scrambled_caveman):
        f = extract_features(gen())
        for k, v in f.to_dict().items():
            assert np.isfinite(v), k
        assert 0.0 <= f.density <= 1.0
        assert 0.0 <= f.row_gini <= 1.0
        assert 0.0 <= f.bandwidth_mean <= 1.0


def test_features_empty_matrix():
    a = HostCSR(np.zeros(9, np.int64), np.zeros(0, np.int32),
                np.zeros(0, np.float32), (8, 8))
    f = extract_features(a)
    assert f.nnz == 0 and np.isfinite(f.consec_jaccard)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------


def test_cache_hit_miss_and_zero_preprocess():
    a = _scrambled_caveman()
    planner = Planner()
    p1 = planner.plan(a, reuse_hint=10)
    assert not p1.from_cache
    p2 = planner.plan(a, reuse_hint=10)
    assert p2.from_cache and p2.preprocess_s == 0.0
    assert p2.reorder == p1.reorder and p2.scheme == p1.scheme
    assert planner.cache.hits == 1 and planner.cache.misses == 1
    # same pattern, new values: still a hit (fingerprint is pattern-keyed)
    a2 = HostCSR(a.indptr, a.indices, a.data * 2.0, a.shape)
    assert planner.plan(a2, reuse_hint=10).from_cache


def test_cache_reuse_buckets_are_separate():
    a = _scrambled_caveman()
    planner = Planner()
    planner.plan(a, reuse_hint=1)
    p = planner.plan(a, reuse_hint=100)       # other bucket: not a hit
    assert not p.from_cache
    assert reuse_bucket(1) != reuse_bucket(100)
    assert reuse_bucket(2) == reuse_bucket(9)


def test_cache_disk_round_trip(tmp_path):
    a = _scrambled_caveman()
    cache = PlanCache(path=str(tmp_path / "plans"))
    planner = Planner(cache=cache)
    p1 = planner.plan(a, reuse_hint=50)
    cache.clear_memory()                       # force the on-disk tier
    p2 = planner.plan(a, reuse_hint=50)
    assert p2.from_cache and p2.preprocess_s == 0.0
    assert p2.reorder == p1.reorder and p2.scheme == p1.scheme
    if p1.perm is not None:
        np.testing.assert_array_equal(p2.perm, p1.perm)
    if p1.boundaries is not None:
        np.testing.assert_array_equal(p2.boundaries, p1.boundaries)
    # a fresh cache object reads the same files
    cache2 = PlanCache(path=str(tmp_path / "plans"))
    p3 = cache2.get(fingerprint(a), 50)
    assert p3 is not None and p3.scheme == p1.scheme


def _plan_of_size(i: int, nrows: int, reuse: int = 10) -> Plan:
    return Plan(fingerprint=f"fp-{i}", reorder="rcm", scheme="fixed",
                reuse_hint=reuse, perm=np.arange(nrows),
                boundaries=np.arange(0, nrows, 8))


def test_cache_lru_byte_budget_evicts_oldest():
    nrows = 1024                      # ≈ 8 KiB perm + 1 KiB boundaries
    per = _plan_of_size(0, nrows).nbytes()
    cache = PlanCache(max_bytes=3 * per)
    for i in range(5):
        cache.put(_plan_of_size(i, nrows))
    assert cache.stats["entries"] == 3
    assert cache.stats["evictions"] == 2
    assert cache.total_bytes <= 3 * per
    # the two oldest are gone, the three newest serve
    assert cache.get("fp-0", 10) is None and cache.get("fp-1", 10) is None
    for i in (2, 3, 4):
        assert cache.get(f"fp-{i}", 10) is not None


def test_cache_lru_get_refreshes_recency():
    nrows = 512
    per = _plan_of_size(0, nrows).nbytes()
    cache = PlanCache(max_bytes=2 * per)
    cache.put(_plan_of_size(0, nrows))
    cache.put(_plan_of_size(1, nrows))
    assert cache.get("fp-0", 10) is not None   # touch 0 → 1 becomes LRU
    cache.put(_plan_of_size(2, nrows))
    assert cache.get("fp-1", 10) is None       # 1 evicted, not 0
    assert cache.get("fp-0", 10) is not None


def test_cache_lru_evicts_disk_tier_too(tmp_path):
    nrows = 512
    per = _plan_of_size(0, nrows).nbytes()
    cache = PlanCache(path=str(tmp_path / "plans"), max_bytes=2 * per)
    for i in range(4):
        cache.put(_plan_of_size(i, nrows))
    files = list((tmp_path / "plans").glob("*.npz"))
    assert len(files) == 2                     # evicted keys removed on disk
    # and a fresh cache object only sees the survivors
    cache2 = PlanCache(path=str(tmp_path / "plans"))
    assert cache2.get("fp-0", 10) is None
    assert cache2.get("fp-3", 10) is not None


def test_cache_budget_bounds_disk_across_restarts(tmp_path):
    """A restarted process inherits the on-disk tier: its budget must
    apply to pre-existing files too (oldest-mtime-first), or the store
    grows by ~budget per restart."""
    import os
    nrows = 512
    per = _plan_of_size(0, nrows).nbytes()
    path = str(tmp_path / "plans")
    c1 = PlanCache(path=path, max_bytes=2 * per)
    c1.put(_plan_of_size(0, nrows))
    c1.put(_plan_of_size(1, nrows))
    os.utime(c1._file(PlanCache.key("fp-0", 10)), (1, 1))   # fp-0 is oldest
    # "restart": a fresh cache writes two more plans under the same budget
    c2 = PlanCache(path=path, max_bytes=2 * per)
    c2.put(_plan_of_size(2, nrows))
    c2.put(_plan_of_size(3, nrows))
    files = list((tmp_path / "plans").glob("*.npz"))
    assert len(files) <= 3                   # not 4: inherited files count
    # and a third restart prunes down to the budget before serving
    c3 = PlanCache(path=path, max_bytes=per)
    assert len(list((tmp_path / "plans").glob("*.npz"))) <= 1
    assert c3.get("fp-0", 10) is None        # the oldest never survives


def test_cache_plan_from_another_backend_misses(tmp_path, monkeypatch):
    """A plan made under one backend (platform, device kind, device
    count) is never served under another — in memory or from disk."""
    from repro.planner import plan_cache
    path = str(tmp_path / "plans")
    monkeypatch.setattr(plan_cache, "backend_tag", lambda: "cpu-cpu-1")
    cache = PlanCache(path=path)
    cache.put(_plan_of_size(0, 64))
    assert cache.get("fp-0", 10) is not None
    monkeypatch.setattr(plan_cache, "backend_tag",
                        lambda: "tpu-TPU-v5-lite-1")
    assert cache.get("fp-0", 10) is None
    assert PlanCache(path=path).get("fp-0", 10) is None
    monkeypatch.setattr(plan_cache, "backend_tag", lambda: "cpu-cpu-1")
    assert PlanCache(path=path).get("fp-0", 10) is not None


def test_cache_unbudgeted_never_evicts():
    cache = PlanCache()
    for i in range(50):
        cache.put(_plan_of_size(i, 256))
    assert cache.stats["entries"] == 50 and cache.stats["evictions"] == 0


def test_cache_workload_keys_are_separate():
    a = _scrambled_caveman()
    planner = Planner()
    p_a2 = planner.plan(a, reuse_hint=10, workload="a2")
    p_spmm = planner.plan(a, reuse_hint=10, workload="spmm")
    assert not p_spmm.from_cache           # a2 plan must not shadow spmm
    assert p_spmm.workload == "spmm" and p_a2.workload == "a2"
    assert planner.plan(a, reuse_hint=10, workload="spmm").from_cache


def test_measured_spmm_workload_probes_spmm_kernels():
    """Tall-skinny coverage: measured mode under workload='spmm' must back
    execute(plan, a, dense_b) with SpMM measurements (keyed separately
    from the A² probes of the same pattern)."""
    a = FAMILIES["blockdiag"]()
    planner = Planner(measure_top=2)
    plan = planner.plan(a, reuse_hint=20, measure=True, workload="spmm")
    assert "original+rowwise" in plan.measured
    fp = fingerprint(a)
    # the measurement landed under the workload-qualified key...
    assert planner.cost_model.measurement(f"{fp}|spmm", IDENTITY) is not None
    # ...and did not masquerade as an A² measurement
    assert planner.cost_model.measurement(fp, IDENTITY) is None
    bd = np.random.default_rng(3).standard_normal(
        (a.ncols, 16)).astype(np.float32)
    np.testing.assert_allclose(planner.execute(plan, a, bd),
                               a.to_dense() @ bd, rtol=1e-3, atol=1e-3)


def test_plan_npz_round_trip_preserves_metadata():
    plan = Plan(fingerprint="fp1-abc", reorder="rcm", scheme="variable",
                reuse_hint=7, max_cluster=8,
                perm=np.arange(5)[::-1].copy(),
                boundaries=np.array([0, 2, 4]),
                preprocess_s=0.5, predicted={"kernel_rel": 0.7},
                measured={"rcm+variable": {"kernel_rel": 0.7,
                                           "preprocess_rel": 0.1}})
    back = Plan.from_npz_bytes(plan.to_npz_bytes())
    assert back.reorder == "rcm" and back.scheme == "variable"
    assert back.reuse_hint == 7 and back.preprocess_s == 0.5
    assert back.predicted == plan.predicted
    assert back.measured == plan.measured
    np.testing.assert_array_equal(back.perm, plan.perm)
    np.testing.assert_array_equal(back.boundaries, plan.boundaries)


# ---------------------------------------------------------------------------
# break-even / amortization
# ---------------------------------------------------------------------------


def test_amortization_calculator():
    # reuse × gain > preprocess
    assert amortizes(10, 0.2, 1.0)
    assert not amortizes(4, 0.2, 1.0)
    assert amortizes(3, 0.5, 0.0)              # free preprocessing
    assert not amortizes(1000, -0.1, 0.5)      # slower kernel never pays
    assert break_even_reuse(0.2, 1.0) == pytest.approx(5.0)
    assert break_even_reuse(0.0, 1.0) == np.inf
    assert break_even_reuse(0.5, 0.0) == 0.0


def test_single_shot_chooses_identity():
    model = CostModel()
    for gen in (lambda: gen_er(256, avg_deg=8, seed=1),
                lambda: gen_mesh2d(16, seed=2),
                lambda: gen_powerlaw(256, avg_deg=8, seed=3),
                _scrambled_caveman):
        f = extract_features(gen())
        chosen = model.choose(f, reuse=1)
        assert chosen.candidate.key == IDENTITY.key


def test_break_even_monotone_in_reuse_hint():
    model = CostModel()
    f = extract_features(_scrambled_caveman())
    prev_set: set[str] = set()
    prev_per_call = np.inf
    for reuse in (1, 2, 5, 10, 20, 50, 100, 500):
        ranked = model.rank(f, reuse)
        amortizing = {s.candidate.key for s in ranked if s.amortizes}
        # the amortizing set only grows with reuse
        assert prev_set <= amortizing, (reuse, prev_set - amortizing)
        prev_set = amortizing
        # the chosen per-call cost only improves with reuse
        chosen = model.choose(f, reuse)
        per_call = chosen.total_rel / reuse
        assert per_call <= prev_per_call + 1e-12
        prev_per_call = per_call


def test_measured_overrides_heuristic():
    a = _scrambled_caveman()
    f = extract_features(a)
    fp = fingerprint(a)
    model = CostModel()
    cand = Candidate("original", "fixed")
    model.observe(fp, IDENTITY, kernel_s=1.0, preprocess_s=0.0)
    model.observe(fp, cand, kernel_s=0.4, preprocess_s=0.3)
    s = model.score(f, cand, reuse=2, fingerprint=fp)
    assert s.measured
    assert s.kernel_rel == pytest.approx(0.4)
    assert s.preprocess_rel == pytest.approx(0.3)
    # measured gain 0.6/call: pays for 0.3 preprocessing within 2 calls
    assert s.amortizes
    assert model.choose(f, 2, fingerprint=fp).candidate.key == cand.key


# ---------------------------------------------------------------------------
# service: execution correctness + never-worse-than-identity
# ---------------------------------------------------------------------------


FAMILIES = {
    "blockdiag": lambda: gen_block_diag(256, block=8, seed=0),
    "caveman_scr": lambda: _scrambled_caveman(256, cave=16, seed=1),
    "er": lambda: gen_er(256, avg_deg=8, seed=2),
    "mesh": lambda: gen_mesh2d(16, seed=3),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_execute_matches_oracle_across_reuse(family):
    a = FAMILIES[family]()
    planner = Planner()
    want = spgemm_reference(a, a)
    for reuse in (1, 50):
        plan = planner.plan(a, reuse_hint=reuse)
        got = planner.execute(plan, a)
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_execute_spmm_and_ab_paths():
    a = FAMILIES["caveman_scr"]()
    planner = Planner()
    plan = planner.plan(a, reuse_hint=50)
    bd = np.random.default_rng(0).standard_normal(
        (a.ncols, 16)).astype(np.float32)
    np.testing.assert_allclose(planner.execute(plan, a, bd),
                               a.to_dense() @ bd, rtol=1e-3, atol=1e-3)
    b = gen_er(a.ncols, avg_deg=6, seed=9)
    np.testing.assert_allclose(planner.execute(plan, a, b),
                               spgemm_reference(a, b),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_planner_never_worse_than_identity_measured(family):
    """Measured mode: total cost of the chosen plan ≤ identity's total —
    identity is always in the shortlist and selection is argmin."""
    a = FAMILIES[family]()
    planner = Planner(measure_top=4)
    reuse = 20
    plan = planner.plan(a, reuse_hint=reuse, measure=True)
    meas = plan.measured
    assert "original+rowwise" in meas          # identity always probed
    ident = meas["original+rowwise"]
    chosen_key = f"{plan.reorder}+{plan.scheme}"
    chosen = meas.get(chosen_key)
    assert chosen is not None, chosen_key
    total = chosen["preprocess_rel"] + reuse * chosen["kernel_rel"]
    total_ident = ident["preprocess_rel"] + reuse * ident["kernel_rel"]
    assert total <= total_ident + 1e-9
    # and the plan still computes the right product
    np.testing.assert_allclose(planner.execute(plan, a),
                               spgemm_reference(a, a), rtol=1e-3, atol=1e-3)


def test_plan_records_predictions_and_identity_fallback():
    a = FAMILIES["er"]()
    planner = Planner()
    plan = planner.plan(a, reuse_hint=1)
    assert plan.is_identity
    assert plan.perm is None and plan.boundaries is None
    assert "total_rel" in plan.predicted


def test_serve_engine_spgemm_server_stats():
    from repro.serve.engine import SpGEMMServer
    a = FAMILIES["blockdiag"]()
    srv = SpGEMMServer(default_reuse_hint=10)
    r1 = srv.submit(a)
    r2 = srv.submit(HostCSR(a.indptr, a.indices, a.data * 0.5, a.shape))
    assert not r1.plan_cache_hit and r2.plan_cache_hit
    assert srv.stats()["requests"] == 2 and srv.stats()["plan_hits"] == 1
    np.testing.assert_allclose(r2.result, 0.25 * spgemm_reference(a, a),
                               rtol=1e-3, atol=1e-3)


def test_pipeline_planned_stages():
    from repro.distributed.pipeline import (pipeline_spmm_apply,
                                            plan_pipeline_stages)
    mats = [gen_block_diag(48, block=8, seed=s) for s in range(2)]
    planner = Planner()
    plans = plan_pipeline_stages(mats, num_microbatches=3, passes=2,
                                 planner=planner)
    assert all(p.reuse_hint == 6 for p in plans)
    x = np.random.default_rng(1).standard_normal((3, 2, 48)).astype(
        np.float32)
    y = pipeline_spmm_apply(plans, mats, x, planner=planner)
    want = x
    for m in mats:
        want = np.einsum("fk,mbk->mbf", m.to_dense(), want)
    np.testing.assert_allclose(y, want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# per-tenant namespaces (ISSUE 4)
# ---------------------------------------------------------------------------


def test_cache_namespaces_partition_keys():
    a = PlanCache(namespace="tenant-a")
    b = PlanCache(namespace="tenant-b")
    plain = PlanCache()
    plan = _plan_of_size(0, 64)
    a.put(plan)
    assert a.get("fp-0", 10) is not None
    assert b.get("fp-0", 10) is None           # other tenant: miss
    assert plain.get("fp-0", 10) is None       # default namespace: miss
    assert a.stats["namespace"] == "tenant-a"


def test_cache_namespace_budget_is_isolated(tmp_path):
    """One tenant flooding a shared directory must not evict another's
    hot plans — each namespace owns (and budgets) only its own files."""
    nrows = 1024
    per = _plan_of_size(0, nrows).nbytes()
    shared = str(tmp_path / "plans")
    a = PlanCache(path=shared, max_bytes=2 * per, namespace="ten-a")
    b = PlanCache(path=shared, max_bytes=2 * per, namespace="ten-b")
    a.put(_plan_of_size(0, nrows))
    for i in range(1, 6):                       # b floods its partition
        b.put(_plan_of_size(i, nrows))
    assert b.stats["evictions"] >= 3
    # a's single plan survives b's churn, in memory AND on disk
    assert a.get("fp-0", 10) is not None
    a2 = PlanCache(path=shared, namespace="ten-a")
    assert a2.get("fp-0", 10) is not None
    # a fresh scan of b's namespace never accounts a's files
    b2 = PlanCache(path=shared, max_bytes=2 * per, namespace="ten-b")
    assert b2.get("fp-0", 10) is None
    assert a2.get("fp-0", 10) is not None


def test_cache_namespace_rejects_unsafe_names():
    with pytest.raises(ValueError):
        PlanCache(namespace="../escape")
    # '_' is the filename separator: 'ns-a_x' files would match namespace
    # 'a''s scan prefix 'ns-a_' and be evicted cross-tenant
    with pytest.raises(ValueError):
        PlanCache(namespace="a_x")


def test_spgemm_server_tenant_namespace():
    from repro.serve.engine import SpGEMMServer
    a = FAMILIES["blockdiag"]()
    srv = SpGEMMServer(default_reuse_hint=10, tenant="team-x")
    srv.submit(a)
    assert srv.stats()["tenant"] == "team-x"
    assert srv.stats()["namespace"] == "team-x"
    assert srv.planner.cache.namespace == "team-x"


# ---------------------------------------------------------------------------
# learned cost-model calibration (ISSUE 4 / ROADMAP open item)
# ---------------------------------------------------------------------------


def _synthetic_samples(n_specs=4, kernel_factor=2.0):
    """Fabricated sweep rows: measured kernel_rel = factor × heuristic
    prediction, over real (generated) suite specs so features exist."""
    from repro.benchlib import representative_subset
    from repro.core.suite import generate
    samples = []
    specs = representative_subset(n_specs)
    for spec in specs:
        f = extract_features(generate(spec))
        for algo, scheme in (("rcm", "fixed"), ("degree", "fixed"),
                             ("rcm", "variable")):
            pred, pre = CostModel._heuristic(f, Candidate(algo, scheme))
            samples.append({"spec": spec.name, "reorder": algo,
                            "scheme": scheme,
                            "kernel_rel": kernel_factor * pred,
                            "preprocess_rel": pre + 0.1})
    return samples


def test_calibration_fits_kernel_scale():
    from repro.planner import fit_calibration
    samples = _synthetic_samples()
    assert len(samples) >= 8
    cal = fit_calibration(samples=samples, min_samples=8, min_key_samples=3)
    assert cal is not None and cal.n_samples == len(samples)
    # measured = 2 × heuristic → fitted slope ≈ 2 for both schemes
    for scheme in ("fixed", "variable"):
        assert cal.kernel_scale[scheme] == pytest.approx(2.0, rel=1e-6)
    # identity anchors never move: rowwise/original are not overridden
    assert "rowwise" not in cal.preprocess_scheme
    assert "original" not in cal.preprocess_reorder


def test_calibration_too_few_samples_falls_back():
    from repro.planner import fit_calibration
    cal = fit_calibration(samples=_synthetic_samples()[:5], min_samples=8)
    assert cal is None


def test_calibrated_cost_model_keeps_identity_invariant():
    from repro.planner import fit_calibration
    cal = fit_calibration(samples=_synthetic_samples(), min_samples=8)
    model = CostModel(calibration=cal)
    a = FAMILIES["caveman_scr"]()
    f = extract_features(a)
    s_id = model.score(f, IDENTITY, 1)
    assert s_id.kernel_rel == 1.0 and s_id.preprocess_rel == 0.0
    assert s_id.amortizes
    # calibrated candidates score 2× the uncalibrated heuristic
    plain = CostModel()
    c = Candidate("rcm", "fixed")
    assert model.score(f, c, 10).kernel_rel == pytest.approx(
        2.0 * plain.score(f, c, 10).kernel_rel, rel=1e-6)


def test_calibration_fits_real_bench_cache_if_present():
    """The committed sweep cache (when present) must fit cleanly — this is
    the exact corpus the ROADMAP item targets."""
    import os
    from repro import benchlib
    from repro.planner import fit_calibration
    if not os.path.exists(benchlib.CACHE_PATH):
        pytest.skip("no accumulated bench cache in this checkout")
    cal = fit_calibration()
    if cal is None:
        pytest.skip("bench cache holds too few samples to fit")
    assert cal.n_samples >= 8
    for v in cal.kernel_scale.values():
        assert 0.25 <= v <= 4.0
    for v in (*cal.preprocess_reorder.values(),
              *cal.preprocess_scheme.values()):
        assert v >= 0.0
