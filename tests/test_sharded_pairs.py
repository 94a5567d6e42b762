"""Multi-core sharded pair stream + B-fetch-deduping revisit order
(ISSUE 5): partitioner edge cases (1 core degenerates bitwise, pair-less
blocks land in exactly one shard with their sentinel), revisit-ordered
output bit-identical to the unordered kernel, counters, balance, and the
planner/cost-model wiring of the sharded variant.

Everything here runs the serial partition (interpret mode / CPU) — the
shard_map dispatch needs one device per shard and is exercised on TPU
backends through the same ``cluster_spgemm_pairs_sharded`` entry point.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # pragma: no cover - container without hypothesis
    from _hypo_shim import given, settings, st

from repro.core.formats import (HostCSR, bcc_from_host, live_pair_counters,
                                partition_balance, partition_pair_stream,
                                partition_pair_stream_reference,
                                revisit_pair_stream, revisit_window_blocks,
                                tiled_csr_from_host)
from repro.core.spgemm import spgemm_reference
from repro.kernels import ops
from repro.kernels.cluster_spgemm import (cluster_spgemm_pairs,
                                          cluster_spgemm_pairs_sharded,
                                          cluster_spgemm_pairs_window)
from repro.kernels.ref import cluster_spgemm_pairs_sharded_ref

pytestmark = pytest.mark.pallas


def rand_host(n, m, density, seed):
    rng = np.random.default_rng(seed)
    dense = (rng.random((n, m)) < density) * rng.uniform(
        0.5, 2.0, (n, m)).astype(np.float32)
    return HostCSR.from_dense(dense.astype(np.float32))


def _pack(a, b, *, block_r=8, block_k=16, bn=16):
    bcc = bcc_from_host(a, block_r=block_r, block_k=block_k)
    tiled = tiled_csr_from_host(b, block_k=block_k, bn=bn)
    stream = ops.bcc_compact_stream(bcc, cover_all_blocks=True)
    pairs = ops.build_live_pairs(bcc, tiled, stream)
    return bcc, tiled, stream, pairs


def _run_pairs(pairs, stream, tiled, nblocks, **kw):
    import jax.numpy as jnp
    return np.asarray(cluster_spgemm_pairs(
        *(jnp.asarray(p) for p in pairs), jnp.asarray(stream[2]),
        tiled.tiles, interpret=True, nblocks=nblocks, nnb=tiled.nnb, **kw))


# ---------------------------------------------------------------------------
# partitioner properties
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(4, 48), st.integers(4, 48), st.floats(0.0, 0.4),
       st.integers(1, 6), st.integers(0, 1000))
def test_property_partition_matches_reference_and_covers(n, m, density,
                                                         shards, seed):
    """Vectorized partitioner is bit-identical to the loop oracle; ranges
    are contiguous, cover every block, and concatenating the shard
    streams (minus tail padding) recovers the input stream."""
    a = rand_host(n, m, density, seed)
    b = rand_host(m, n, density, seed + 7)
    _, _, _, pairs = _pack(a, b)
    nblocks = (a.nrows + 7) // 8
    r1, sp1 = partition_pair_stream(pairs, nblocks=nblocks,
                                    num_shards=shards)
    r2, sp2 = partition_pair_stream_reference(pairs, nblocks=nblocks,
                                              num_shards=shards)
    np.testing.assert_array_equal(r1, r2)
    for p1, p2 in zip(sp1, sp2):
        for x, y in zip(p1, p2):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # contiguous cover of 0..nblocks
    assert r1[0, 0] == 0 and r1[-1, 1] == nblocks
    assert np.all(r1[1:, 0] == r1[:-1, 1])
    assert np.all(r1[:, 1] > r1[:, 0])          # every shard owns a block
    # concatenated shard streams (stripping each shard's zero-slot tail
    # padding) == the original stream
    cat = [np.concatenate(cols) for cols in zip(*[
        tuple(np.asarray(c) for c in p) for p in sp1])]
    keep = []
    off = 0
    for (sb, sj, ss, sa), (start, end) in zip(sp1, r1):
        t = sb.shape[0]
        # padding repeats the last pair with slot 0; count real steps by
        # matching against the original stream's per-range slice
        lo = int(np.searchsorted(np.asarray(pairs[0]), start, "left"))
        hi = int(np.searchsorted(np.asarray(pairs[0]), end, "left"))
        keep.extend(range(off, off + (hi - lo)))
        off += t
    for got_col, want_col in zip(cat, pairs):
        np.testing.assert_array_equal(got_col[keep], np.asarray(want_col))


def test_partition_one_shard_is_bitwise_identity():
    a = rand_host(40, 40, 0.15, 3)
    _, _, _, pairs = _pack(a, a)
    ranges, sp = partition_pair_stream(pairs, nblocks=(a.nrows + 7) // 8,
                                       num_shards=1)
    assert ranges.tolist() == [[0, (a.nrows + 7) // 8]]
    for got, want in zip(sp[0], pairs):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pairless_block_sentinel_lands_in_exactly_one_shard():
    """Rows 8..15 form an empty A block; B's columns beyond tile (0, 0)
    are dead. Every pair-less block's zero-slot sentinel must appear in
    exactly one shard (the one owning its block range)."""
    dense_a = np.zeros((48, 32), np.float32)
    dense_a[0, 5] = 1.0
    dense_a[44, 2] = 3.0
    dense_b = np.zeros((32, 32), np.float32)
    dense_b[np.arange(8), np.arange(8)] = 2.0
    a, b = HostCSR.from_dense(dense_a), HostCSR.from_dense(dense_b)
    _, _, _, pairs = _pack(a, b)
    nblocks = (a.nrows + 7) // 8
    ranges, sp = partition_pair_stream(pairs, nblocks=nblocks, num_shards=3)
    for blk in range(nblocks):
        owners = [i for i, (s, e) in enumerate(ranges) if s <= blk < e]
        assert len(owners) == 1
        sb, sj, ss, sa = (np.asarray(c) for c in sp[owners[0]])
        # the block appears in its owner's sub-stream (sentinel included)
        assert np.any(sb == blk)
        # and in no other shard
        for i, p in enumerate(sp):
            if i != owners[0]:
                assert not np.any(np.asarray(p[0]) == blk)
    # blocks with no live pair carry a zero-slot sentinel step
    blocks_np, _, slots_np, _ = (np.asarray(c) for c in pairs)
    pairless = set(range(nblocks)) - set(blocks_np[slots_np > 0].tolist())
    assert pairless, "fixture must contain pair-less blocks"
    for blk in pairless:
        assert np.any((blocks_np == blk) & (slots_np == 0))


def test_partition_ties_take_the_earliest_block():
    """Blocks 0 and 1 hold no live pair, so every boundary up to block 2
    is equally far from the half-way count: the first one wins, as in
    the loop reference (hypothesis found n=22, m=16, seed=1)."""
    pairs = ([0, 1, 2, 2, 2, 2, 2, 2], [0] * 8, [0, 0, 1, 0, 0, 0, 0, 0],
             [0, 1, 2, 2, 2, 2, 2, 2])
    ranges, _ = partition_pair_stream(pairs, nblocks=3, num_shards=2)
    ref, _ = partition_pair_stream_reference(pairs, nblocks=3,
                                             num_shards=2)
    assert ranges.tolist() == ref.tolist() == [[0, 1], [1, 3]]


def test_num_shards_clipped_to_nblocks():
    a = rand_host(16, 16, 0.3, 4)          # 2 row blocks
    _, _, _, pairs = _pack(a, a)
    ranges, sp = partition_pair_stream(pairs, nblocks=2, num_shards=8)
    assert len(sp) == 2 and ranges.shape == (2, 2)


# ---------------------------------------------------------------------------
# sharded kernel parity (serial partition — the off-TPU dispatch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [1, 2, 3, 5])
def test_sharded_kernel_bitwise_matches_unsharded(shards):
    import jax.numpy as jnp
    a = rand_host(64, 48, 0.12, 11)
    b = rand_host(48, 64, 0.12, 12)
    bcc, tiled, stream, pairs = _pack(a, b)
    nblocks = (a.nrows + 7) // 8
    base = _run_pairs(pairs, stream, tiled, nblocks, block_r=8, block_k=16,
                      bn=16)
    ranges, sp = partition_pair_stream(pairs, nblocks=nblocks,
                                       num_shards=shards)
    got = np.asarray(cluster_spgemm_pairs_sharded(
        sp, ranges, jnp.asarray(stream[2]), tiled.tiles, block_r=8,
        block_k=16, bn=16, nblocks=nblocks, nnb=tiled.nnb, interpret=True))
    np.testing.assert_array_equal(got, base)
    want = cluster_spgemm_pairs_sharded_ref(
        sp, ranges, stream[2], np.asarray(tiled.tiles), block_r=8,
        block_k=16, bn=16, nblocks=nblocks, nnb=tiled.nnb)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ops_wrapper_sharded_and_revisit_parity():
    """bcc_spgemm_tiled(shards=…, revisit=…) — the serving entry point —
    matches the reference for every knob combination."""
    a = rand_host(56, 40, 0.15, 21)
    b = rand_host(40, 56, 0.15, 22)
    bcc, tiled, _, _ = _pack(a, b)
    want = spgemm_reference(a, b)
    for kw in ({"shards": 2}, {"shards": 3, "revisit": True},
               {"shards": 1, "revisit": True},
               {"shards": 2, "resident": True}):
        got = np.asarray(ops.bcc_spgemm_tiled(bcc, tiled, interpret=True,
                                              **kw))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=str(kw))


# ---------------------------------------------------------------------------
# revisit order: bit-identity + counter reduction
# ---------------------------------------------------------------------------


def _revisit(pairs, tiled, nblocks, *, block_r=8, bn=16):
    wb = min(revisit_window_blocks(tiled.nnb, block_r=block_r, bn=bn),
             max(nblocks, 1))
    return revisit_pair_stream(pairs, window_blocks=wb), wb


@pytest.mark.parametrize("n,k,density,seed", [
    (40, 48, 0.10, 0),
    (64, 64, 0.05, 1),
    (17, 33, 0.15, 3),      # maximally ragged
])
def test_revisit_ordered_kernel_bitwise_matches_unordered(n, k, density,
                                                          seed):
    import jax.numpy as jnp
    a = rand_host(n, k, density, seed)
    b = rand_host(k, n, density, seed + 31)
    bcc, tiled, stream, pairs = _pack(a, b)
    nblocks = (a.nrows + 7) // 8
    base = _run_pairs(pairs, stream, tiled, nblocks, block_r=8, block_k=16,
                      bn=16)
    rv, wb = _revisit(pairs, tiled, nblocks)
    wins = (np.asarray(rv[0]).astype(np.int64) // wb).astype(np.int32)
    got = np.asarray(cluster_spgemm_pairs_window(
        jnp.asarray(wins), *(jnp.asarray(p) for p in rv),
        jnp.asarray(stream[2]), tiled.tiles, block_r=8, block_k=16, bn=16,
        nblocks=nblocks, nnb=tiled.nnb, window_blocks=wb, interpret=True))
    np.testing.assert_array_equal(got, base)


def test_revisit_stream_is_window_sorted_permutation():
    a = rand_host(64, 64, 0.1, 40)
    _, tiled, _, pairs = _pack(a, a)
    nblocks = (a.nrows + 7) // 8
    rv, wb = _revisit(pairs, tiled, nblocks)
    # a permutation of the input triples
    key = lambda p: sorted(zip(*(np.asarray(c).tolist() for c in p)))
    assert key(rv) == key(pairs)
    blocks, js, slots, _ = (np.asarray(c) for c in rv)
    wins = blocks.astype(np.int64) // wb
    assert np.all(np.diff(wins) >= 0)          # windows non-decreasing
    # within a window, (j, slot) non-decreasing lexicographically
    wkey = (wins * tiled.nnb + js) * (int(slots.max()) + 2) + slots
    assert np.all(np.diff(wkey) >= 0)
    # and the dedup actually reduces refetches on this pattern
    c0 = live_pair_counters(pairs, block_r=8, block_k=16, bn=16)
    c1 = live_pair_counters(rv, block_r=8, block_k=16, bn=16)
    assert c1["b_tile_refetches"] < c0["b_tile_refetches"]


def test_counters_b_fetch_units_and_balance():
    """Hand-sized check of the new counters (units per COUNTER_UNITS):
    fetches count elision-aware runs of live slots, refetches the excess
    over one fetch per distinct tile; b_bytes = fetches × tile bytes."""
    blocks = [0, 0, 0, 1, 1, 1]
    js = [0, 0, 1, 0, 1, 1]
    slots = [2, 2, 3, 2, 3, 0]       # run-elided: 2 | 3 | 2 | 3 (+pad)
    a_idx = [0, 1, 1, 2, 2, 2]
    c = live_pair_counters((blocks, js, slots, a_idx), block_r=8,
                           block_k=16, bn=16)
    assert c["b_tile_fetches"] == 4
    assert c["b_distinct_tiles"] == 2
    assert c["b_tile_refetches"] == 2
    assert c["b_bytes"] == 4 * 16 * 16 * 4
    assert c["mxu_issues"] == 5
    # balance: a 2-shard split of this stream at the block boundary
    ranges, sp = partition_pair_stream((blocks, js, slots, a_idx),
                                       nblocks=2, num_shards=2, pad_to=1)
    assert partition_balance(sp) == max(3, 2) / (5 / 2)


def test_quick_tier_partition_balance_and_refetch_reduction():
    """Stream-level acceptance on a quick-tier slice (host-only, no
    kernels): 4-way partition within 20% of ideal, revisit ordering
    reduces B tile refetches ≥ 1.15× (the bench gates the full tier)."""
    from repro.benchlib import representative_subset
    from repro.core.suite import generate
    for spec in representative_subset(4):
        a = generate(spec)
        bcc = bcc_from_host(a, block_r=8, block_k=128)
        tiled = tiled_csr_from_host(a, 128, 128)
        stream = ops.bcc_compact_stream(bcc, cover_all_blocks=True)
        pairs = ops.build_live_pairs(bcc, tiled, stream)
        nblocks = (a.nrows + 7) // 8
        _, sp = partition_pair_stream(pairs, nblocks=nblocks, num_shards=4)
        assert partition_balance(sp) <= 1.2, spec.name
        rv, _ = _revisit(pairs, tiled, nblocks, bn=128)
        c0 = live_pair_counters(pairs, block_r=8, block_k=128)
        c1 = live_pair_counters(rv, block_r=8, block_k=128)
        ratio = max(c0["b_tile_refetches"], 1) \
            / max(c1["b_tile_refetches"], 1)
        assert ratio >= 1.15, (spec.name, ratio)


@pytest.mark.slow
def test_quick_tier_revisit_bitwise_parity():
    """Acceptance: revisit-ordered output is bit-identical to the
    unordered kernel across the quick-tier families (interpret mode is
    minutes-slow at suite sizes, hence the slow marker)."""
    from repro.benchlib import representative_subset
    from repro.core.suite import generate
    for spec in representative_subset(8):
        a = generate(spec)
        bcc, tiled, stream, pairs = _pack(a, a, block_k=128, bn=128)
        nblocks = (a.nrows + 7) // 8
        base = _run_pairs(pairs, stream, tiled, nblocks, block_r=8,
                          block_k=128, bn=128)
        got = np.asarray(ops.bcc_spgemm_tiled(
            bcc, tiled, interpret=True, revisit=True, resident=False))
        np.testing.assert_array_equal(
            got, base[: a.nrows, : a.ncols], err_msg=spec.name)


def test_shard_map_dispatch_multi_device_subprocess():
    """The real shard_map dispatch (one device per shard) is bit-identical
    to the serial partition. Needs >1 device, so it runs in a subprocess
    with XLA's host-platform device-count override — the closest CI can
    get to a multi-core TPU."""
    import os
    import subprocess
    import sys
    prog = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "assert jax.device_count() == 4, jax.device_count()\n"
        "from repro.core.formats import (HostCSR, bcc_from_host,\n"
        "    tiled_csr_from_host, partition_pair_stream)\n"
        "from repro.kernels import ops\n"
        "from repro.kernels.cluster_spgemm import (cluster_spgemm_pairs,\n"
        "    cluster_spgemm_pairs_sharded)\n"
        "r = np.random.default_rng(5)\n"
        "dense = ((r.random((64, 64)) < 0.15)\n"
        "         * r.uniform(0.5, 2.0, (64, 64))).astype(np.float32)\n"
        "a = HostCSR.from_dense(dense)\n"
        "bcc = bcc_from_host(a, block_r=8, block_k=16)\n"
        "tiled = tiled_csr_from_host(a, block_k=16, bn=16)\n"
        "stream = ops.bcc_compact_stream(bcc, cover_all_blocks=True)\n"
        "pairs = ops.build_live_pairs(bcc, tiled, stream)\n"
        "kw = dict(block_r=8, block_k=16, bn=16, nblocks=8, nnb=tiled.nnb)\n"
        "base = np.asarray(cluster_spgemm_pairs(\n"
        "    *(jnp.asarray(p) for p in pairs), jnp.asarray(stream[2]),\n"
        "    tiled.tiles, interpret=True, **kw))\n"
        "ranges, sp = partition_pair_stream(pairs, nblocks=8, num_shards=4)\n"
        "got = np.asarray(cluster_spgemm_pairs_sharded(\n"
        "    sp, ranges, jnp.asarray(stream[2]), tiled.tiles,\n"
        "    interpret=True, use_shard_map=True, **kw))\n"
        "assert np.array_equal(got, base), 'shard_map mismatch'\n"
        "print('OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# planner wiring: cost model shard term + service shard_pack
# ---------------------------------------------------------------------------


def test_cost_model_shard_term(monkeypatch):
    """With a multi-core TPU backend the pallas kernel_rel divides by the
    per-core step count (× the balance-gated efficiency)."""
    from repro.planner import cost_model as cm
    from repro.planner import extract_features
    a = rand_host(64, 64, 0.2, 60)
    f = extract_features(a)
    cand = cm.Candidate("original", "pallas")
    monkeypatch.setattr(cm, "_pallas_on_tpu", lambda: True)
    monkeypatch.setattr(cm, "_pallas_core_count", lambda: 1)
    one, _ = cm.CostModel._heuristic(f, cand)
    monkeypatch.setattr(cm, "_pallas_core_count", lambda: 4)
    four, _ = cm.CostModel._heuristic(f, cand)
    assert four < one
    assert four == pytest.approx(
        max(one / (cm.PALLAS_SHARD_EFFICIENCY * 4), 0.15 / 4))
    # non-pallas schemes are untouched by the core count
    r1, _ = cm.CostModel._heuristic(f, cm.IDENTITY)
    assert r1 == 1.0


def test_cost_model_shard_term_gated_on_compact_grid(monkeypatch):
    """A matrix too wide for the compacted grid's C strip budget runs the
    single-stream padded grid — it must not collect the per-core
    discount, however many cores the backend has."""
    from repro.planner import cost_model as cm
    from repro.planner.features import extract_features
    wide = HostCSR.from_coo([0, 3, 7], [10, 69000, 123], [1.0, 2.0, 3.0],
                            (64, 70000))
    assert not cm._pallas_compact_ok(wide.ncols)
    f = extract_features(wide)
    cand = cm.Candidate("original", "pallas")
    monkeypatch.setattr(cm, "_pallas_on_tpu", lambda: True)
    monkeypatch.setattr(cm, "_pallas_core_count", lambda: 1)
    one, _ = cm.CostModel._heuristic(f, cand)
    monkeypatch.setattr(cm, "_pallas_core_count", lambda: 4)
    four, _ = cm.CostModel._heuristic(f, cand)
    assert four == one


def test_service_packs_shard_partition(monkeypatch):
    """On a multi-core backend the serving path packs the shard partition
    once per cached operand and the sharded execute stays correct."""
    from repro.planner import Planner
    from repro.planner.features import fingerprint
    from repro.planner.plan_cache import Plan
    monkeypatch.setattr(ops, "pallas_shard_count", lambda: 2)
    a = rand_host(48, 48, 0.15, 70)
    planner = Planner()
    plan = Plan(fingerprint=fingerprint(a), reorder="original",
                scheme="pallas", reuse_hint=10)
    got = planner.execute(plan, a)
    np.testing.assert_allclose(got, spgemm_reference(a, a),
                               rtol=1e-3, atol=1e-3)
    packed = [v for v in planner._exec_cache.values() if v[0] == "pallas"]
    assert packed and packed[0][5] is not None      # shard_pack cached
    ranges, sp, wb = packed[0][5]
    assert len(sp) == 2 and wb is None
