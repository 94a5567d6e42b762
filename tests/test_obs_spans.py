"""Spans of one served request: the front end, host↔device transfers
and compiles.

Covers:
  * one request, one trace: ``admit`` opens it on the client's thread;
    ``queue``, ``validate``, ``request`` and everything under them join
    it on the worker's, and ``SpGEMMResponse.trace_id`` names it;
  * ``upload``/``fetch`` byte counts equal the ``nbytes`` of what the
    SpMM (Â·X) and Sp×Sp (A·A) Pallas paths move, counted here on the
    host from the same packed operands;
  * a fresh ``jit`` gives exactly one ``compile`` span, in a trace of its
    own; a warm repeat gives none;
  * with the tracer disabled a served request records nothing;
  * the default ring holds a benchmark window of requests undropped.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import (HostCSR, bcc_from_host, select_block_k,
                                tiled_csr_from_host)
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer, get_tracer
from repro.planner import Candidate, Planner
from repro.planner.features import fingerprint
from repro.planner.plan_cache import Plan, PlanCache
from repro.resilience.policy import ResiliencePolicy
from repro.serve.engine import SpGEMMServer
from repro.serve.frontend import AsyncSpGEMMServer

HINT = 64


def _graph(n=64, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    d = (rng.random((n, n)) < density) * rng.random((n, n))
    return HostCSR.from_dense(d.astype(np.float32))


def _revalued(a: HostCSR, seed: int) -> HostCSR:
    """Same pattern, new values (an exec-cache miss: packs again)."""
    data = np.random.default_rng(seed).random(a.nnz).astype(np.float32)
    return HostCSR(a.indptr, a.indices, data, (a.nrows, a.ncols))


def _server(a: HostCSR, workload: str, workers: int = 1):
    """The serving path with the Pallas plan for ``a`` in the cache."""
    planner = Planner(cache=PlanCache(), resilience=ResiliencePolicy(),
                      candidates=(Candidate("original", "pallas"),))
    planner.cache.put(Plan(fingerprint=fingerprint(a), reorder="original",
                           scheme="pallas", reuse_hint=HINT,
                           workload=workload))
    return AsyncSpGEMMServer(SpGEMMServer(planner=planner),
                             workers=workers)


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


def _serve_twice(workload: str, tracer: Tracer):
    """Warm one request, then trace a second one with new operand
    values; returns (response, its spans, the operands it was sent)."""
    a = _graph()
    x = np.random.default_rng(1).standard_normal(
        (a.nrows, 16)).astype(np.float32)
    srv = _server(a, workload)
    try:
        srv.submit_wait(a, x if workload == "spmm" else None,
                        reuse_hint=HINT)
        sent = ((a, np.random.default_rng(2).standard_normal(
            (a.nrows, 16)).astype(np.float32)) if workload == "spmm"
            else (_revalued(a, 3), None))
        tracer.clear()
        resp = srv.submit_wait(*sent, reuse_hint=HINT)
    finally:
        srv.close()
    return resp, tracer.spans(), sent


@pytest.mark.parametrize("workload", ["spmm", "a2"])
def test_one_request_is_one_trace_across_threads(workload, traced):
    resp, spans, _ = _serve_twice(workload, traced)
    assert resp.scheme == "pallas" and resp.trace_id
    fam = [s for s in spans if s.trace_id == resp.trace_id]
    by_id = {s.span_id: s for s in fam}
    assert all(s.parent_id in by_id for s in fam if s.parent_id)

    def one(name):
        got = [s for s in fam if s.name == name]
        assert len(got) == 1, (name, [s.name for s in fam])
        return got[0]

    def parents(name):
        return {by_id[s.parent_id].name for s in fam if s.name == name}

    admit, request = one("admit"), one("request")
    assert admit.parent_id == 0
    assert admit.attrs == {"tenant": "", "coalesced": False, "shed": ""}
    for name in ("queue", "validate", "request"):
        assert parents(name) == {"admit"}
    # admit runs on the client's thread, the rest on the worker's
    assert one("queue").thread_id == request.thread_id != admit.thread_id
    assert one("queue").t0 >= admit.t0 and "depth" in one("queue").attrs
    assert one("validate").attrs == {"skipped_a": workload == "spmm"}
    assert parents("sync") == {"kernel"}
    assert parents("guard") == {"execute"}
    assert parents("kernel_variant") == {"kernel"}
    assert "kernel" in parents("fetch") and "kernel" in parents("upload")
    if workload == "spmm":
        # X uploads in execute, the stream at the launch; nothing packs
        assert parents("upload") == {"execute", "kernel"}
        assert one("kernel_variant").attrs == {"variant": "spmm_compact"}
    else:
        # BCC and tiles upload while packing, BCC comes back for the
        # compact stream
        assert parents("upload") == {"pack", "kernel"}
        assert parents("fetch") == {"pack", "kernel"}
    # the shapes were warm: nothing compiled
    assert not [s for s in spans if s.name == "compile"]


def _bytes(spans, name, parent=None):
    by_id = {s.span_id: s for s in spans}
    return sum(s.attrs["bytes"] for s in spans if s.name == name and (
        parent is None or by_id[s.parent_id].name == parent))


def test_transfer_bytes_of_the_spmm_path(traced):
    resp, spans, (a, x) = _serve_twice("spmm", traced)
    stream = ops.bcc_compact_stream(bcc_from_host(a),
                                    cover_all_blocks=True)
    assert _bytes(spans, "upload", "execute") == x.nbytes
    assert _bytes(spans, "upload", "kernel") == sum(s.nbytes
                                                    for s in stream)
    assert _bytes(spans, "fetch") == resp.result.nbytes == a.nrows * 16 * 4


def test_transfer_bytes_of_the_a2_path(traced):
    resp, spans, (a, _) = _serve_twice("a2", traced)
    bk = select_block_k(a)
    bcc = bcc_from_host(a, block_k=bk)
    tiled = tiled_csr_from_host(a, block_k=bk)
    stream = ops.bcc_compact_stream(bcc, cover_all_blocks=True)
    pairs = ops.build_live_pairs(bcc, tiled, stream)
    kv = next(s for s in spans if s.name == "kernel_variant")
    assert kv.attrs["variant"] in ("resident", "streamed", "streamed_db")

    def nbytes(*arrays):
        return sum(np.asarray(x).nbytes for x in arrays)

    bcc_b = nbytes(bcc.tile_ids, bcc.values, bcc.ntiles)
    assert _bytes(spans, "upload", "pack") == bcc_b + nbytes(tiled.tiles,
                                                             tiled.table)
    assert _bytes(spans, "upload", "kernel") == nbytes(stream[2], *pairs)
    # the BCC read back for the compact stream, ntiles and B's table for
    # the live pairs, then C
    assert _bytes(spans, "fetch", "pack") == bcc_b + nbytes(bcc.ntiles,
                                                            tiled.table)
    assert _bytes(spans, "fetch", "kernel") == resp.result.nbytes \
        == a.nrows * a.ncols * 4


def test_fresh_jit_compiles_once_in_a_trace_of_its_own(traced):
    def triple_plus_one(v):
        return v * 3.0 + 1.0
    f = jax.jit(triple_plus_one)
    x = jnp.arange(7.0)
    traced.clear()
    with traced.span("request"):
        f(x).block_until_ready()
    comp = [s for s in traced.spans() if s.name == "compile"]
    assert len(comp) == 1
    req = next(s for s in traced.spans() if s.name == "request")
    assert comp[0].parent_id == 0 and comp[0].trace_id != req.trace_id
    assert "triple_plus_one" in comp[0].attrs["fun_name"]
    assert 0 < comp[0].duration <= req.duration
    traced.clear()
    f(x).block_until_ready()
    assert not [s for s in traced.spans() if s.name == "compile"]


def test_disabled_tracer_records_nothing_across_a_served_request(
        monkeypatch):
    tracer = get_tracer()
    monkeypatch.setattr(tracer, "enabled", False)
    calls = []
    monkeypatch.setattr(tracer, "_record", calls.append)
    a = _graph(seed=4)
    x = np.ones((a.nrows, 8), np.float32)
    srv = _server(a, "spmm")
    try:
        resp = srv.submit_wait(a, x, reuse_hint=HINT)
        resp2 = srv.submit_wait(_revalued(a, 5), None, reuse_hint=HINT)
    finally:
        srv.close()
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()   # a compile
    assert calls == []
    assert resp.trace_id == "" and resp2.trace_id == ""


def test_default_ring_holds_a_window_of_requests(monkeypatch):
    """Serve, at the default capacity, more requests than the fastest
    cell completes in a 51 s window (p50 0.137 s: about 372), with every
    span this path records: none is dropped."""
    fresh = Tracer(enabled=True)
    monkeypatch.setattr(obs_trace, "_TRACER", fresh)
    a = _graph(n=32, density=0.2, seed=6)
    x = np.ones((a.nrows, 8), np.float32)
    srv = _server(a, "spmm", workers=0)
    try:
        srv.submit_wait(a, x, reuse_hint=HINT)
        fresh.clear()
        srv.submit_wait(a, x + 1.0, reuse_hint=HINT)
        per_request = len(fresh.spans())
        n = 2 * 372
        for k in range(n - 1):
            srv.submit_wait(a, x + float(k), reuse_hint=HINT)
    finally:
        srv.close()
        fresh.disable()
    assert per_request >= 13
    assert fresh.dropped == 0
    assert len(fresh.spans()) == n * per_request
    assert fresh.capacity >= n * per_request
