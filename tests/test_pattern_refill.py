"""Pattern-keyed packing of the Pallas Sp×Sp operands.

The planner packs A·B once per pattern (``SpGEMMPattern``) and, for each
new value set, refills A's stream values and B's tiles on the device.
Covers:
  * the slabs and tiles a refilled request launches on are byte for
    byte those of a full host pack of the same operands
    (``bcc_from_host`` → ``bcc_compact_stream``, ``tiled_csr_from_host``),
    and its product is the launch on the full pack's arrays, over value
    sets that change and come back, a permuted plan, an A·B pair, empty
    row blocks with tail padding, a repeated (row, col) entry, bf16 B
    tiles and patterns packed at row blocks 16 and 32 high, each refill
    of those also against float64 scipy;
  * the route is chosen once, at the pack, from what the pattern shows:
    one case per route the CPU can take, each against
    ``spgemm_reference``;
  * one pattern packs once whatever its values: ``exec_cache_refills``
    counts the value changes, the same values twice refill nothing, and
    the exec cache holds one entry;
  * a refill uploads only the operands' values and reads nothing back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import (HostCSR, bcc_from_host, bcc_layout,
                                scatter_map, select_block_k,
                                tiled_csr_from_host)
from repro.core.spgemm import spgemm_reference
from repro.kernels import ops
from repro.kernels.cluster_spgemm import _stack_shard_streams
from repro.obs.metrics import get_registry
from repro.obs.trace import get_tracer
from repro.planner import Planner
from repro.planner.features import fingerprint
from repro.planner.plan_cache import Plan
from repro.resilience.policy import ResiliencePolicy

from _packing import grouped, merge, scipy_product

pytestmark = pytest.mark.pallas


def _random(n, m, density, seed):
    rng = np.random.default_rng(seed)
    d = (rng.random((n, m)) < density) * rng.uniform(0.5, 1.5, (n, m))
    return HostCSR.from_dense(d.astype(np.float32))


def _revalued(h: HostCSR, seed: int) -> HostCSR:
    """Same pattern, new values; seed 0 keeps ``h``'s own."""
    if seed == 0:
        return h
    data = np.random.default_rng(seed).uniform(0.5, 1.5, h.nnz)
    return HostCSR(h.indptr, h.indices, data.astype(np.float32), h.shape)


def _with_empty_blocks() -> HostCSR:
    """Row blocks 1, 2 and 5 of 8 rows empty; elsewhere a band inside
    each 128-column strip, so the compact stream needs cover steps and
    tail padding, and C's windows are sparse enough for the sparse-C
    route (one strip of three per row block)."""
    d = np.zeros((376, 376), np.float32)
    for i in range(376):
        if i // 8 not in (1, 2, 5):
            lo, hi = i // 128 * 128, i // 128 * 128 + 128
            d[i, max(lo, i - 3): min(hi, i + 4)] = 1.0 + i / 100
    return HostCSR.from_dense(d)


def _with_repeat() -> HostCSR:
    """Row 2 holds its first column twice, the second time with 7.0: a
    numpy fill keeps the later value."""
    a = _random(40, 40, 0.15, 3)
    rows = np.repeat(np.arange(40), a.row_nnz()).tolist() + [2]
    cols = a.indices.tolist() + [int(a.indices[a.indptr[2]])]
    vals = a.data.tolist() + [7.0]
    order = np.lexsort((np.arange(len(rows)), cols, rows))
    rows, cols, vals = (np.asarray(x)[order] for x in (rows, cols, vals))
    indptr = np.searchsorted(rows, np.arange(41))
    return HostCSR(indptr, cols, vals, (40, 40))


def _case(name):
    """(A, B or None, perm or None, B's tile dtype)."""
    f32 = jnp.float32
    if name == "values":
        return _random(64, 64, 0.2, 0), None, None, f32
    if name == "perm":
        a = _random(96, 96, 0.08, 1)
        return a, None, np.random.default_rng(5).permutation(96), f32
    if name == "ab":
        return (_random(48, 80, 0.1, 2), _random(80, 136, 0.08, 4),
                np.random.default_rng(6).permutation(48), f32)
    if name == "empty_blocks":
        return _with_empty_blocks(), None, None, f32
    if name == "repeat":
        return _with_repeat(), None, None, f32
    if name == "bf16":
        return _random(64, 64, 0.2, 7), None, None, jnp.bfloat16
    if name in HEIGHTS:
        g = HEIGHTS[name]
        return grouped(2048, g, 128, g), merge(2048, 128, g), None, f32
    raise KeyError(name)


# cases whose patterns select row blocks taller than 8, and the height
HEIGHTS = {"height16": 16, "height32": 32}
CASES = ("values", "perm", "ab", "empty_blocks", "repeat", "bf16",
         *HEIGHTS)


def _full_pack(a, b, perm, dtype, block_r):
    """The operands packed whole on the host, as a full pack does: A's
    compact stream slabs of ``block_r`` rows, read back from its BCC, and
    B's TiledCSR."""
    if perm is None:
        ap = a
    elif b is None:
        ap = a.permute_symmetric(perm)
    else:
        ap = a.permute_rows(perm)
    bh = ap if b is None else b
    bk = select_block_k(bh)
    values = ops.bcc_compact_stream(
        bcc_from_host(ap, block_r=block_r, block_k=bk),
        cover_all_blocks=True)[2]
    return values, tiled_csr_from_host(bh, block_k=bk, dtype=dtype)


def _unpermuted(c, b, perm):
    """The plan's product ``c`` in the operands' own order."""
    if perm is None:
        return c
    out = np.empty_like(c)
    if b is None:
        out[np.ix_(perm, perm)] = c
    else:
        out[perm] = c
    return out


def _product(a, b=None):
    """``a @ (b or a)`` through a pattern of its own, in the operands'
    order."""
    b = a if b is None else b
    pattern = ops.pack_spgemm_pattern(a, b, block_k=select_block_k(b))
    return np.asarray(pattern.run(*pattern.fill(a.data, b.data)))


def _planner(dtype=None):
    return Planner(pallas_b_dtype=dtype,
                   resilience=ResiliencePolicy.disabled())


def _plan(a, perm=None):
    return Plan(fingerprint=fingerprint(a),
                reorder="original" if perm is None else "rcm",
                scheme="pallas", reuse_hint=10, perm=perm)


def _count(name):
    reg = get_registry()
    return reg.snapshot().get(reg._key(name, {}), 0)


@pytest.mark.parametrize("case", CASES)
def test_refilled_product_is_bit_identical_to_a_full_pack(case):
    a, b, perm, dtype = _case(case)
    planner = _planner(dtype)
    plan = _plan(a, perm)
    for seed in (0, 1, 2, 0):
        ai = _revalued(a, seed)
        bi = None if b is None else _revalued(b, seed + 10)
        got = planner.execute(plan, ai, bi)
        (_, pattern, (_, values, tiled)), = planner._exec_cache.values()
        full_values, full_tiled = _full_pack(ai, bi, perm, dtype,
                                             pattern.a.block_r)
        np.testing.assert_array_equal(np.asarray(values), full_values)
        np.testing.assert_array_equal(np.asarray(tiled.tiles),
                                      np.asarray(full_tiled.tiles))
        np.testing.assert_array_equal(np.asarray(tiled.table),
                                      np.asarray(full_tiled.table))
        full = ops.bcc_spgemm_tiled(pattern, jnp.asarray(full_values),
                                    full_tiled)
        np.testing.assert_array_equal(
            got, _unpermuted(np.asarray(full), bi, perm))
    assert len(planner._exec_cache) == 1


@pytest.mark.parametrize("case", list(HEIGHTS))
def test_refill_at_a_selected_height_matches_float64_scipy(case):
    """The pattern packs at the height its row groups select, and every
    refill of it, new values or old ones again, is scipy's product."""
    get_registry().reset()
    a, b, _, _ = _case(case)
    planner = _planner()
    plan = _plan(a)
    for seed in (0, 1, 0):
        ai, bi = _revalued(a, seed), _revalued(b, seed + 10)
        got = planner.execute(plan, ai, bi)
        (_, pattern, _), = planner._exec_cache.values()
        assert pattern.a.block_r == HEIGHTS[case]
        np.testing.assert_allclose(got, scipy_product(ai, bi), rtol=1e-5,
                                   atol=1e-6)
    assert _count("exec_cache_refills") == 2


def test_empty_blocks_case_has_cover_steps_tail_padding_and_sparse_c():
    a = _with_empty_blocks()
    _, ntiles, tpb, _ = bcc_layout(a, 8, select_block_k(a))
    keep, live = ops._compact_keep(ntiles, tpb, cover_all_blocks=True)
    assert (ntiles == 0).sum() == 3 and keep.size > live
    assert ops.pack_spgemm_pattern(
        a, a, block_k=select_block_k(a)).route == "sparse_c"


@pytest.mark.parametrize("route", ["padded", "resident", "streamed",
                                   "sharded", "sparse_c"])
def test_route_is_chosen_from_what_the_pattern_shows(route, monkeypatch):
    """Each route the CPU can take, chosen by the pack from the widths,
    budgets, cores and C density it observes, and recorded in the
    pattern; the product through the pattern is the reference's."""
    a = _with_empty_blocks() if route == "sparse_c" else _random(
        64, 64, 0.2, 14)
    if route == "padded":
        monkeypatch.setattr(ops, "_COMPACT_C_STRIP_BUDGET", 0)
    if route == "streamed":
        monkeypatch.setattr(ops, "_RESIDENT_B_BUDGET", 0)
    if route == "sharded":
        monkeypatch.setattr(ops, "pallas_shard_count", lambda: 2)
    pattern = ops.pack_spgemm_pattern(a, a, block_k=select_block_k(a))
    assert pattern.route == route
    got = np.asarray(pattern.run(*pattern.fill(a.data)))
    np.testing.assert_allclose(got, spgemm_reference(a, a), rtol=1e-5,
                               atol=1e-5)


def test_repeated_entry_keeps_the_value_written_last():
    a = _with_repeat()
    row = a.indices[a.indptr[2]: a.indptr[3]]
    assert row[0] == row[1]
    dense = np.asarray(bcc_from_host(a, block_k=128).to_dense())
    assert dense[2, row[0]] == 7.0


def test_one_pattern_packs_once_and_refills_on_new_values():
    get_registry().reset()
    a = _random(64, 64, 0.2, 8)
    planner = _planner()
    plan = _plan(a)
    # (value seed, exec_cache_refills after the request)
    for seed, refills in ((0, 0), (0, 0), (1, 1), (1, 1), (2, 2), (0, 3)):
        planner.execute(plan, _revalued(a, seed))
        assert _count("exec_cache_packs") == 1
        assert _count("exec_cache_refills") == refills
        assert len(planner._exec_cache) == 1
    # a new pattern is a new entry, packed once more
    other = _random(64, 64, 0.2, 9)
    planner.execute(_plan(other), other)
    assert _count("exec_cache_packs") == 2
    assert len(planner._exec_cache) == 2


@pytest.fixture
def traced():
    tracer = get_tracer()
    was = tracer.enabled
    tracer.clear()
    tracer.enable()
    yield tracer
    tracer.clear()
    if not was:
        tracer.disable()


def test_refill_uploads_the_values_and_reads_nothing_back(traced):
    a = _random(64, 64, 0.2, 11)
    planner = _planner()
    plan = _plan(a)
    planner.execute(plan, a)
    traced.clear()
    planner.execute(plan, a)
    assert not [s for s in traced.spans() if s.name == "pack"]
    traced.clear()
    planner.execute(plan, _revalued(a, 3))
    spans = traced.spans()
    by_id = {s.span_id: s for s in spans}
    pack, = [s for s in spans if s.name == "pack"]
    assert pack.attrs["kind"] == "refill"
    under_pack = [s for s in spans if s.parent_id == pack.span_id]
    assert [s.name for s in under_pack] == ["upload"]
    assert under_pack[0].attrs["bytes"] == a.nnz * 4
    # the launch moves nothing up; the only copy down is C
    ups = [s for s in spans if s.name == "upload"
           and by_id[s.parent_id].name == "kernel"]
    assert sum(s.attrs["bytes"] for s in ups) == 0
    fetched = [s.attrs["bytes"] for s in spans if s.name == "fetch"]
    assert fetched == [a.nrows * a.ncols * 4]


def test_permuted_source_maps_the_sent_values():
    a = _random(30, 30, 0.2, 12)
    perm = np.random.default_rng(0).permutation(30)
    for symmetric in (True, False):
        ap, src = a.permuted(perm, symmetric=symmetric)
        want = (a.permute_symmetric(perm) if symmetric
                else a.permute_rows(perm))
        np.testing.assert_array_equal(ap.indices, want.indices)
        np.testing.assert_array_equal(ap.data, want.data)
        np.testing.assert_array_equal(a.data[src], want.data)


def test_scatter_map_keeps_one_writer_per_position():
    pos = np.array([9, 3, 9, 0, 3, 7])
    src, dst = scatter_map(pos)
    assert dst.tolist() == [0, 3, 7, 9]
    assert src.tolist() == [3, 4, 5, 2]
    out = np.zeros(10)
    out[pos] = np.arange(6.0)
    np.testing.assert_array_equal(out[dst], np.arange(6.0)[src])


def test_device_shard_streams_stack_like_host_ones():
    rng = np.random.default_rng(0)
    shards = [tuple(rng.integers(0, 9, n).astype(np.int32)
                    for _ in range(4)) for n in (5, 8, 3)]
    host = _stack_shard_streams(shards)
    dev = _stack_shard_streams([tuple(jnp.asarray(x) for x in p)
                                for p in shards])
    assert all(isinstance(d, jax.Array) for d in dev)
    for h, d in zip(host, dev):
        np.testing.assert_array_equal(h, np.asarray(d))


def test_concurrent_value_sets_never_mix():
    """Worker threads share one entry and refill its value slot in turn:
    each request still gets the product of its own values."""
    import sys
    import threading
    a = _random(48, 48, 0.2, 13)
    planner = _planner()
    plan = _plan(a)
    sets = [_revalued(a, s) for s in range(3)]
    want = [_product(x) for x in sets]
    wrong, done = [], []

    def worker(k):
        for i in range(12):
            s = (k + i) % 3
            if not np.array_equal(planner.execute(plan, sets[s]), want[s]):
                wrong.append((k, i))
        done.append(k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(8)) and not wrong
