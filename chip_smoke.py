"""Bring-up smoke test: serve sparse products on a TPU through the
serving path's own entry points, and check every answer.

    python chip_smoke.py              # phases (a)-(d), one chip
    python chip_smoke.py --chips 4    # phase (a) sharded over four chips

The deployment is the Graph500 generator (R-MAT with a=0.57, b=c=0.19,
edge factor 16) at scale 14: n = 16,384 and nnz ~ 442k, with random
values from ``--seed``. Requests go through
``AsyncSpGEMMServer.submit_wait``. Each phase sends three requests: the
first is cold (packing and compilation), the other two hit the plan and
exec caches.

  (a) A·A pinned to the Pallas scheme.
  (b) A·A routed by the planner's default candidates, at scale 12
      (n = 4,096, the largest kind of suite matrix). At scale 14 the
      planner routes A·A to an XLA scatter scheme that takes about eight
      minutes per request on a v5e, over this script's time budget.
  (c) Chained products through the sparse-C kernel: A·A (``hops=1``) at
      scale 14, and A·A·A (``hops=2``) at scale 12.
  (d) SpMM A·X with 128 dense features, pinned to the Pallas scheme.

A pinned plan is put into the planner's plan cache, which is where the
serving path reads plans from. The planner falls back to the identity
plan whenever its candidates do not amortize, so a candidate list alone
cannot force a scheme.

Every result is compared with ``scipy.sparse`` in float64 (rtol 1e-4,
atol 1e-4 * max|C|). The run fails at the first wrong answer, resilience
incident, degraded response, or pinned request not served by Pallas. Only
then is the last line of stdout the JSON result. Without a TPU, the
script exits non-zero before doing any work.

``--chips 4`` runs phase (a) on the default multi-chip path, which shards
the pair stream over all four chips. It compares that result with the
one-chip kernel (the pattern packed for one core, on device 0) and with
scipy, and checks that the sharded output spans the four devices.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

SCALE = 14            # Graph500 scale of the main operand
SUITE_SCALE = 12      # phase (b) and the hops=2 chain's operand
EDGE_FACTOR = 16
FEATURES = 128        # dense columns of the SpMM phase
REQUESTS = 3
REUSE_HINT = 20
RTOL = 1e-4


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def _scipy(h):
    import scipy.sparse as sp
    return sp.csr_matrix((np.asarray(h.data, np.float64), h.indices,
                          h.indptr), shape=h.shape)


def _check(name: str, got, ref) -> float:
    """Largest |got - ref|; fails when an element is off by more than
    ``atol + RTOL*|ref|`` with ``atol = RTOL * max|ref|``, or is not
    finite. ``got`` is a dense array or a HostCSR; ``ref`` is a scipy
    matrix, or a dense array for SpMM."""
    import scipy.sparse as sp
    if got.shape != ref.shape:
        _fail(f"{name}: result shape {got.shape}, expected {ref.shape}")
    if not sp.issparse(ref):
        diff = np.abs(np.asarray(got, np.float64) - ref)
        bound = RTOL * np.abs(ref).max() + RTOL * np.abs(ref)
        if not (diff <= bound).all():       # also false for NaN
            _fail(f"{name}: off by up to {diff.max()}")
        return float(diff.max(initial=0.0))
    ref = ref.tocsr()
    atol = RTOL * abs(ref).max()
    if isinstance(got, np.ndarray):
        # on the reference's pattern, compare values; off it the answer
        # is 0, so the largest |got| there is the error
        rows = np.repeat(np.arange(ref.shape[0]), np.diff(ref.indptr))
        on = got[rows, ref.indices]
        rest = np.array(got)
        rest[rows, ref.indices] = 0
        off = float(np.abs(rest).max(initial=0.0))
        del rest
        diff = np.abs(on.astype(np.float64) - ref.data)
        ok = (diff <= atol + RTOL * np.abs(ref.data)).all() and off <= atol
        worst = float(np.max([diff.max(initial=0.0), off]))
    else:
        d = abs(_scipy(got) - ref)
        # |d| <= atol + RTOL|ref| everywhere iff this never exceeds atol
        excess = (d - RTOL * abs(ref)).max()
        worst = float(d.max())
        ok = excess <= atol and np.isfinite(worst)
    if not ok:                              # NaN fails every comparison
        _fail(f"{name}: off by up to {worst} (atol {atol})")
    return worst


def _launches() -> dict:
    from repro.obs import metrics
    return {k: v for k, v in metrics.get_registry().snapshot().items()
            if k.startswith("kernel_launches")}


def _variants(before: dict) -> dict:
    """Kernel launches by variant since ``before``."""
    out = {}
    for k, v in _launches().items():
        n = v - before.get(k, 0)
        if n:
            out[k.split("variant=")[-1].rstrip("}")] = n
    return out


def _server(policy, pins=()):
    """An inline (``workers=0``) front-end over a fresh planner. ``pins``
    are ``(operand, workload)`` pairs whose plan is the Pallas scheme in
    original order; a server with pins only ever plans Pallas."""
    from repro.planner import Candidate, Planner
    from repro.planner.features import fingerprint
    from repro.planner.plan_cache import Plan, PlanCache
    from repro.serve.batcher import BatchPolicy
    from repro.serve.engine import SpGEMMServer
    from repro.serve.frontend import AsyncSpGEMMServer
    kw = {}
    if pins:
        kw["candidates"] = (Candidate("original", "pallas"),)
    planner = Planner(cache=PlanCache(), resilience=policy, **kw)
    for op, workload in pins:
        planner.cache.put(Plan(fingerprint=fingerprint(op),
                               reorder="original", scheme="pallas",
                               reuse_hint=REUSE_HINT, workload=workload))
    return AsyncSpGEMMServer(SpGEMMServer(planner=planner), workers=0,
                             batch_policy=BatchPolicy(enabled=False))


def _serve(name: str, server, policy, request, ref, *, pinned: bool,
           launches: dict | None = None):
    """Send ``REQUESTS`` requests, check each response and result, and
    print the phase line. ``launches``: the kernel launches each request
    must make, by variant. Returns the last response."""
    before = _launches()
    times, worst, resp = [], 0.0, None
    for i in range(REQUESTS):
        t0 = time.perf_counter()
        resp = request(server)
        times.append(time.perf_counter() - t0)
        if policy.incidents:
            _fail(f"{name}: incident {policy.incidents[-1]}")
        if resp.degraded or resp.downgraded:
            _fail(f"{name}: request {i} degraded "
                  f"(fallback {resp.fallback_scheme!r})")
        if pinned and resp.kernel_path != "pallas":
            _fail(f"{name}: pinned request served by {resp.kernel_path}")
        if i and not resp.plan_cache_hit:
            _fail(f"{name}: warm request {i} missed the plan cache")
        worst = max(worst, _check(name, resp.result, ref))
    variants = _variants(before)
    if launches is not None:
        want = {k: v * REQUESTS for k, v in launches.items()}
        if variants != want:
            _fail(f"{name}: kernel launches {variants}, expected {want}")
    print(f"phase {name}: scheme={resp.reorder}+{resp.scheme} "
          f"variant={','.join(sorted(variants)) or 'xla'} "
          f"cold_s={times[0]} warm_s={times[1:]} max_abs_err={worst}",
          flush=True)
    return resp


def _kron(scale: int, seed: int):
    from repro.core.suite import gen_kron
    a = gen_kron(scale, EDGE_FACTOR, seed)
    return a, _scipy(a)


def phase_a(seed: int, scale: int = SCALE, *, variant: str = "streamed_db"):
    """``variant``: the Sp×Sp kernel the request must launch — on one
    chip, the double-buffered stream (B is far over the VMEM budget and
    nearly every C window is live, so neither the resident nor the
    sparse-C kernel applies)."""
    from repro.resilience.policy import ResiliencePolicy
    a, a64 = _kron(scale, seed)
    ref = a64 @ a64
    policy = ResiliencePolicy()
    server = _server(policy, [(a, "a2")])
    resp = _serve("a (A*A, pallas)", server, policy,
                  lambda s: s.submit_wait(a, reuse_hint=REUSE_HINT), ref,
                  pinned=True, launches={variant: 1})
    return a, ref, resp.result


def phase_b(seed: int, scale: int = SUITE_SCALE):
    from repro.resilience.policy import ResiliencePolicy
    a, a64 = _kron(scale, seed)
    policy = ResiliencePolicy()
    _serve("b (A*A, planner)", _server(policy), policy,
           lambda s: s.submit_wait(a, reuse_hint=REUSE_HINT), a64 @ a64,
           pinned=False)


def phase_c(seed: int, scale: int = SCALE, chain_scale: int = SUITE_SCALE):
    from repro.core.formats import HostCSR
    from repro.resilience.policy import ResiliencePolicy
    a, a64 = _kron(scale, seed)
    policy = ResiliencePolicy()
    _serve("c (A^2 chain, hops=1)", _server(policy, [(a, "chain")]), policy,
           lambda s: s.submit_wait(a, hops=1, reuse_hint=REUSE_HINT),
           a64 @ a64, pinned=True, launches={"sparse_c": 1})
    del a, a64
    gc.collect()
    # hops=2: the second hop plans the first hop's product, so that
    # pattern is pinned too
    a, a64 = _kron(chain_scale, seed)
    c1 = (a64 @ a64).tocsr()
    c1.sort_indices()
    hop1 = HostCSR(c1.indptr.astype(np.int64), c1.indices.astype(np.int32),
                   c1.data.astype(np.float32), c1.shape)
    _serve("c (A^3 chain, hops=2)",
           _server(policy, [(a, "chain"), (hop1, "chain")]), policy,
           lambda s: s.submit_wait(a, hops=2, reuse_hint=REUSE_HINT),
           c1 @ a64, pinned=True, launches={"sparse_c": 2})


def phase_d(seed: int, scale: int = SCALE):
    from repro.resilience.policy import ResiliencePolicy
    a, a64 = _kron(scale, seed)
    x = np.random.default_rng(seed + 1).standard_normal(
        (a.ncols, FEATURES)).astype(np.float32)
    policy = ResiliencePolicy()
    _serve("d (A*X, pallas)", _server(policy, [(a, "spmm")]), policy,
           lambda s: s.submit_wait(a, x, reuse_hint=REUSE_HINT),
           np.asarray(a64 @ x.astype(np.float64)), pinned=True,
           launches={"spmm_compact": 1})


def four_chips(seed: int, scale: int = SCALE):
    """Phase (a) on the default sharded path, then the same product
    through the kernel tier directly: the pattern packed for every chip
    and for one chip (device 0), bit for bit."""
    from unittest import mock

    import jax
    from repro.core.formats import select_block_k
    from repro.kernels import ops
    a, ref, served = phase_a(seed, scale, variant="sharded")
    bk = select_block_k(a)

    def product():
        pattern = ops.pack_spgemm_pattern(a, a, block_k=bk)
        return pattern.route, jax.block_until_ready(
            pattern.run(*pattern.fill(a.data)))
    with jax.default_device(jax.devices()[0]):
        route, sharded = product()
        with mock.patch.object(ops, "pallas_shard_count", lambda: 1):
            one_route, one = product()
    if route != "sharded" or one_route == "sharded":
        _fail(f"routes {route} (four cores) and {one_route} (one core)")
    spans = sorted(d.id for d in sharded.sharding.device_set)
    if len(spans) != len(jax.devices()):
        _fail(f"sharded output spans devices {spans}")
    one_on = sorted(d.id for d in one.sharding.device_set)
    sharded, one = np.asarray(sharded), np.asarray(one)
    if not (np.array_equal(sharded, one) and np.array_equal(served, one)):
        _fail("sharded result differs from the one-chip result")
    print(f"four chips: sharded output spans devices {spans} "
          f"({sharded.shape}); one core on devices {one_on}; served, "
          f"sharded and one-chip results bit-identical; "
          f"max_abs_err={_check('four chips', one, ref)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: phase (a) sharded over four chips, only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    print(f"device: platform={devs[0].platform} kind={kind} "
          f"count={len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs as many devices",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        for phase in (phase_a, phase_b, phase_c, phase_d):
            phase(args.seed)
            gc.collect()
    print(f"all phases passed in {time.perf_counter() - t0} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
