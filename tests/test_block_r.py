"""The Sp×Sp row-block height chosen per pattern
(``ops.select_block_r``): the stream lengths it reads
(``formats.block_r_counts``) against the streams a pack builds at every
height, the heights it picks for dense-tile, scattered, identity and
banded patterns, the C row-strip cap of the dense-strip routes, and what
a pack records of the height it chose."""
import doctest

import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import formats
from repro.core.formats import (HostCSR, block_r_counts, select_block_k,
                                tiled_layout)
from repro.core.segment import rank_in_segment
from repro.core.suite import gen_kron
from repro.kernels import ops
from repro.obs.metrics import get_registry
from repro.obs.trace import get_tracer

from _packing import grouped, merge, scipy_product

HEIGHTS = ops._BLOCK_R_HEIGHTS
DENSE_STRIP = ("resident", "streamed", "streamed_db", "sharded")


def _host(m) -> HostCSR:
    m = sp.csr_matrix(m, dtype=np.float32)
    m.sort_indices()
    return HostCSR(m.indptr.astype(np.int64), m.indices.astype(np.int32),
                   m.data, m.shape)


def _counts(a, b, block_k, bn=128):
    nnb = (b.ncols + bn - 1) // bn
    return block_r_counts(a, tiled_layout(b, block_k, bn)[0],
                          block_k=block_k, nnb=nnb), nnb


def _picks(a, b, block_k):
    counts, nnb = _counts(a, b, block_k)
    return [ops.select_block_r(counts, route=route, nrows=a.nrows,
                               block_k=block_k, nnb=nnb)
            for route in ("streamed", "sparse_c")]


@pytest.mark.parametrize("fn", [formats.block_r_counts, ops.select_block_r,
                                formats.compacted_c_positions],
                         ids=lambda f: f.__name__)
def test_docstring_examples(fn):
    runner = doctest.DocTestRunner()
    for test in doctest.DocTestFinder().find(fn, globs=vars(
            ops if fn is ops.select_block_r else formats)):
        runner.run(test)
    result = runner.summarize(verbose=False)
    assert result.attempted > 0 and result.failed == 0


def _rect(n, m, density, seed):
    rng = np.random.default_rng(seed)
    d = (rng.random((n, m)) < density) * rng.uniform(0.5, 1.5, (n, m))
    return HostCSR.from_dense(d.astype(np.float32))


def _with_empty_blocks():
    d = _rect(200, 300, 0.05, 3).to_dense()
    d[40:72] = 0.0                  # row blocks empty at every height
    d[150:] = 0.0
    return HostCSR.from_dense(d)


def _pair_case(name):
    """(A, B, block_k, bn) of a product whose streams are counted."""
    if name == "kron":
        a = gen_kron(9, edge_factor=8, seed=2)
        return a, a, 128, 128
    if name == "kron_bk16":
        a = gen_kron(8, edge_factor=6, seed=4)
        return a, a, 16, 16
    if name == "rect":
        return _rect(90, 200, 0.04, 1), _rect(200, 70, 0.05, 2), 16, 16
    if name == "empty_blocks":
        return _with_empty_blocks(), _rect(300, 50, 0.1, 5), 32, 16
    if name == "grouped":
        return grouped(256, 16, 16, 7), _rect(256, 64, 0.3, 8), 16, 16
    raise KeyError(name)


@pytest.mark.parametrize("case", ["kron", "kron_bk16", "rect",
                                  "empty_blocks", "grouped"])
def test_one_pass_counts_equal_the_streams_at_every_height(case):
    """The counts of one sort at height 8 are the lengths of the compact
    stream and of ``live_pair_stream`` a pack builds at each height, and
    the live windows whose share ``predict_c_window_density`` gives."""
    a, b, block_k, bn = _pair_case(case)
    table = tiled_layout(b, block_k, bn)[0]
    nnb = (b.ncols + bn - 1) // bn
    counts = block_r_counts(a, table, block_k=block_k, nnb=nnb)
    assert sorted(counts) == list(HEIGHTS)
    for r, (steps, pairs, windows) in counts.items():
        ids, ntiles, _, values_shape = ops._compact_layout(a, r, block_k)
        nblocks = ntiles.shape[0]
        stream = ops._live_pairs(ids, ntiles, table, nnb=nnb,
                                 nblocks=nblocks)
        assert steps == values_shape[0] == ids[0].size, r
        assert pairs == stream[0].size, r
        assert windows == round(ops.predict_c_window_density(
            stream, nblocks=nblocks, nnb=nnb) * nblocks * nnb), r


def test_dense_tile_pattern_picks_the_tallest_height():
    """Every (block, k-tile, column tile) of the scale-9 Graph500-like
    graph is live, as in the revalue cell: the tallest height streams
    each B tile once per 128 rows, not 8."""
    a = gen_kron(9, edge_factor=16, seed=0)
    assert _picks(a, a, select_block_k(a)) == [128, 128]


def test_scattered_pattern_keeps_8():
    """Rows of one 128-row block in 128 distinct k-tiles: a taller block
    merges no tile, so it buys only zero-filled A slabs."""
    n = 2**14
    i = np.arange(n)
    a = _host(sp.csr_matrix((np.ones(n), (i, i % 128 * 128 + i // 128)),
                            shape=(n, n)))
    assert _picks(a, _host(sp.identity(n)), 128) == [8, 8]


@pytest.mark.parametrize("name", ["identity", "band"])
def test_identity_and_band_take_the_tallest_height(name):
    """A slab of r rows of the identity holds r nonzeros at any r: A's
    bytes stay, the pairs fall with the height. A narrow band adds a
    k-tile at a block's edges only."""
    n = 4096
    m = (sp.identity(n) if name == "identity"
         else sp.diags([1.0] * 7, range(-3, 4), shape=(n, n)))
    a = _host(m)
    assert _picks(a, a, 128) == [128, 128]


@pytest.mark.parametrize("nnb, cap", [(32, 128), (64, 64), (128, 32),
                                      (256, 16), (512, 8)])
def test_wide_b_caps_the_dense_strip_height_not_sparse_c(nnb, cap):
    """A dense-tile A against a B ``nnb`` column tiles wide: the C row
    strip ``r·nnb·128·4`` of the dense-strip routes stays within its
    budget; the sparse-C window ``(r, 128)`` does not bind; the padded
    grid keeps 8."""
    a = _rect(1024, 256, 0.5, 9)
    counts = block_r_counts(a, np.ones(2 * nnb, np.int32), block_k=128,
                            nnb=nnb)
    kw = dict(nrows=1024, block_k=128, nnb=nnb)
    for route in DENSE_STRIP:
        got = ops.select_block_r(counts, route=route, **kw)
        assert got == cap, route
        assert ops.compact_grid_ok_ncols(nnb * 128, block_r=got)
    assert ops.select_block_r(counts, route="sparse_c", **kw) == 128
    assert ops.select_block_r(counts, route="padded", **kw) == 8


@pytest.mark.parametrize("shards, cap", [(1, 128), (2, 64), (4, 32),
                                         (16, 8)])
def test_sharded_route_keeps_blocks_for_every_core(shards, cap):
    """The sharded route cuts the stream at block boundaries: it keeps
    ``_SHARD_BLOCKS`` row blocks for each core, the other routes do
    not."""
    a = _rect(1024, 256, 0.5, 10)
    counts, nnb = _counts(a, _rect(256, 256, 0.5, 11), 128)
    kw = dict(nrows=1024, block_k=128, nnb=nnb, shards=shards)
    got = ops.select_block_r(counts, route="sharded", **kw)
    assert got == cap
    assert 1024 // got >= ops._SHARD_BLOCKS * shards or got == 8
    assert ops.select_block_r(counts, route="streamed", **kw) == 128


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


@pytest.mark.parametrize("g", [16, 32])
def test_pack_records_and_reports_the_height(g, traced):
    """The pattern carries the chosen height, the ``sxs_block_r`` counter
    counts the pack under its route and height, the launch's
    ``kernel_variant`` span names it, and the product is scipy's."""
    reg = get_registry()
    reg.reset()
    a = grouped(2048, g, 128, g, integer=True)
    b = merge(2048, 128, g)
    bk = select_block_k(b)
    pattern = ops.pack_spgemm_pattern(a, b, block_k=bk)
    assert pattern.a.block_r == g
    key = reg._key("sxs_block_r", {"route": pattern.route,
                                   "block_r": str(g)})
    assert reg.snapshot()[key] == 1
    traced.clear()
    got = np.asarray(pattern.run(*pattern.fill(a.data, b.data)))
    variant, = [s for s in traced.spans() if s.name == "kernel_variant"]
    assert variant.attrs["block_r"] == g
    np.testing.assert_array_equal(got, scipy_product(a, b))


def _masked_case(name):
    """(A, B, mask, block_k, bn) of a masked product whose streams are
    counted: the mask's windows cut some pairs at every height."""
    if name == "kron_lower":
        # A·A reaches every window; its lower triangle, none above
        a = gen_kron(9, edge_factor=8, seed=2)
        low = _host(sp.tril(sp.csr_matrix((a.data, a.indices, a.indptr),
                                          shape=a.shape)))
        return a, a, low, 128, 128
    if name == "rect":
        return (_rect(90, 200, 0.04, 1), _rect(200, 70, 0.05, 2),
                _rect(90, 70, 0.02, 12), 16, 16)
    if name == "empty_blocks":
        mask = _rect(200, 50, 0.01, 13).to_dense()
        mask[100:] = 0.0
        return (_with_empty_blocks(), _rect(300, 50, 0.1, 5),
                HostCSR.from_dense(mask), 32, 16)
    raise KeyError(name)


@pytest.mark.parametrize("case", ["kron_lower", "rect", "empty_blocks"])
def test_masked_counts_equal_the_pruned_streams_at_every_height(case):
    """With a mask, the counts are the lengths of the live-pair stream a
    masked pack builds (only the pairs of windows holding a nonzero of
    the mask) and the windows it reaches, never more than unmasked."""
    a, b, mask, block_k, bn = _masked_case(case)
    table = tiled_layout(b, block_k, bn)[0]
    nnb = (b.ncols + bn - 1) // bn
    full = block_r_counts(a, table, block_k=block_k, nnb=nnb)
    counts = block_r_counts(a, table, block_k=block_k, nnb=nnb, mask=mask,
                            bn=bn)
    assert any(counts[r][1] < full[r][1] for r in HEIGHTS)
    for r, (steps, pairs, windows, unmasked) in counts.items():
        assert steps == full[r][0] and pairs <= full[r][1]
        assert unmasked == full[r][1]
        ids, ntiles, _, _ = ops._compact_layout(a, r, block_k)
        nblocks = ntiles.shape[0]
        live = formats.mask_windows(mask, block_r=r, bn=bn,
                                    nblocks=nblocks, nnb=nnb)
        stream = ops._live_pairs(ids, ntiles, table, nnb=nnb,
                                 nblocks=nblocks, window_live=live)
        ref = formats.live_pair_stream_reference(
            np.asarray(ids[0]), np.asarray(ids[1]), table, nnb=nnb,
            nblocks=nblocks, window_live=live,
            step_live=rank_in_segment(ids[0].astype(np.int64))
            < ntiles[ids[0]])
        for x, y in zip(stream, ref):
            np.testing.assert_array_equal(x, y)
        assert pairs == stream[0].size, r
        assert windows == round(ops.predict_c_window_density(
            stream, nblocks=nblocks, nnb=nnb) * nblocks * nnb), r


def test_masked_pack_chooses_its_height_on_the_masked_counts():
    """A masked pack takes the sparse-C route at the height
    ``select_block_r`` gives the masked counts, and records the lengths
    of its stream and of the unmasked one at that height."""
    a, b, mask, block_k, bn = _masked_case("kron_lower")
    table = tiled_layout(b, block_k, bn)[0]
    nnb = (b.ncols + bn - 1) // bn
    counts = block_r_counts(a, table, block_k=block_k, nnb=nnb, mask=mask)
    want = ops.select_block_r(counts, route="sparse_c", nrows=a.nrows,
                              block_k=block_k, nnb=nnb)
    pattern = ops.pack_spgemm_pattern(a, b, block_k=block_k, mask=mask)
    assert pattern.route == "sparse_c" and pattern.a.block_r == want
    unmasked = block_r_counts(a, table, block_k=block_k, nnb=nnb)
    assert pattern.mask_pairs == (counts[want][1], unmasked[want][1])
    assert counts[want][3] == unmasked[want][1]
