"""The planner service: ``plan_spgemm(A, reuse_hint) -> Plan`` and
``execute(plan, A, B)``.

This is the layer that turns the repo's menu of 10 reorderings × 3
clusterings into a *decision*: extract features, rank candidates with the
amortization-aware cost model, optionally measure a shortlist on the real
matrix, materialize the winner (permutation + cluster boundaries — the
expensive part), and cache the whole plan under the matrix's pattern
fingerprint so the cost is paid once per pattern, not once per call.

Typical serving flow::

    plan = plan_spgemm(a, reuse_hint=50)      # cache miss: preprocesses
    c    = execute(plan, a)                   # A² under the chosen scheme
    ...
    plan2 = plan_spgemm(a2, reuse_hint=50)    # same pattern: cache hit,
                                              # zero preprocessing

``execute`` accepts ``b=None`` (the paper's A² workload), a second
``HostCSR`` (general SpGEMM) or a dense ``(ncols, width)`` array (the
tall-skinny SpMM workload) and always returns the product in the
*original* row/column order — permutations are internal to the plan.

``execute_chain`` is the chained-product entry point (A³, Markov steps,
MoE routing masks, AMG's Galerkin product R·A·P): it picks the
association from the operands' shapes, plans each hop's left operand,
and — on pallas-scheme hops — runs the sparse-C tier, so that an
intermediate comes back to the host as a ``HostCSR`` of its symbolic
pattern without a dense materialization.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import (DEFAULT_MAX_CLUSTER,
                                   fixed_length_clusters,
                                   hierarchical_clusters,
                                   variable_length_clusters)
from repro.core.formats import (HostCSR, csr_cluster_from_host,
                                csr_from_host, select_block_k)
from repro.core.reorder import reorder as apply_reorder
from repro.core.spgemm import (length_bins, slot_rows_host,
                               spgemm_clusterwise_dense_binned,
                               spgemm_rowwise_dense_binned, spmm_clusterwise,
                               spmm_rowwise)
from repro.core.transfer import device_nbytes, to_device, to_host
from repro.kernels import ops as kernel_ops
from repro.obs import audit as obs_audit
from repro.obs import metrics as obs_metrics
from repro.obs.trace import get_tracer
from repro.planner.cost_model import (Candidate, CostModel,
                                      DEFAULT_CANDIDATES, IDENTITY,
                                      Measurement, ScoredCandidate)
from repro.planner.features import extract_features, fingerprint
from repro.planner.plan_cache import (DEFAULT_CACHE_DIR, DEFAULT_MAX_BYTES,
                                      Plan, PlanCache)
from repro.resilience import faults as _faults
from repro.resilience.errors import (LadderExhaustedError,
                                     NonFiniteOutputError, ProbeTimeoutError)
from repro.resilience.policy import (ResiliencePolicy, fallback_chain,
                                     get_policy)

__all__ = ["Planner", "plan_spgemm", "execute", "execute_chain",
           "chain_order", "default_planner", "reset_default_planner"]


# ---------------------------------------------------------------------------
# plan materialization: run the chosen reorder + clustering for real
# ---------------------------------------------------------------------------


def _materialize(a: HostCSR, cand: Candidate,
                 max_cluster: int = DEFAULT_MAX_CLUSTER,
                 reorder_cache: Optional[dict] = None
                 ) -> tuple[Optional[np.ndarray], Optional[np.ndarray],
                            int, float]:
    """Returns (perm, boundaries, max_cluster, wall seconds).

    ``reorder_cache`` ({reorder name: (reordered matrix, perm)}) shares a
    materialized reordering across the scheme probes of one planning pass
    — a reorder is paid once per matrix, not once per candidate.
    """
    t0 = time.perf_counter()
    perm: Optional[np.ndarray] = None
    boundaries: Optional[np.ndarray] = None
    if cand.scheme == "hierarchical":
        cl = hierarchical_clusters(a, max_cluster_th=max_cluster)
        perm, boundaries = cl.perm, cl.boundaries
    else:
        work = a
        if cand.reorder != "original":
            hit = (reorder_cache or {}).get(cand.reorder)
            if hit is not None:
                work, perm = hit
            else:
                work, perm = apply_reorder(a, cand.reorder)
                if reorder_cache is not None:
                    reorder_cache[cand.reorder] = (work, perm)
        if cand.scheme == "fixed":
            boundaries = fixed_length_clusters(work, max_cluster).boundaries
        elif cand.scheme == "variable":
            boundaries = variable_length_clusters(
                work, max_cluster_th=max_cluster).boundaries
        # "pallas" needs no boundaries: its clusters are the fixed
        # block_r-row blocks of the BCC packing (the format is built at
        # execute time, per operand values)
    return perm, boundaries, max_cluster, time.perf_counter() - t0


def _value_digest(h: HostCSR) -> str:
    """Cheap digest of a matrix's numeric values (pattern excluded)."""
    d = hashlib.blake2b(digest_size=8)
    d.update(np.ascontiguousarray(h.data, dtype=np.float32).tobytes())
    return d.hexdigest()


def _plan_digest(plan: Plan) -> str:
    """Digest of what determines a plan's packed layout: scheme params,
    the permutation and the cluster boundaries. Two plans on the same
    fingerprint may still differ in all of these (replans, per-call
    candidate overrides), so the exec cache must key on them. Memoized on
    the plan — perm/boundaries never change after materialization, and
    the serving hot path calls this per execute."""
    memo = getattr(plan, "_layout_digest", None)
    if memo is not None:
        return memo
    d = hashlib.blake2b(digest_size=8)
    d.update(f"{plan.reorder}|{plan.scheme}|{plan.max_cluster}".encode())
    if plan.perm is not None:
        d.update(np.ascontiguousarray(plan.perm, dtype=np.int64).tobytes())
    if plan.boundaries is not None:
        d.update(np.ascontiguousarray(plan.boundaries,
                                      dtype=np.int64).tobytes())
    out = d.hexdigest()
    plan._layout_digest = out
    return out


class _SingleFlight:
    """Per-key mutual exclusion with refcounted cleanup: concurrent
    planners of the same (fingerprint, workload) serialize, so a thundering
    herd on a cold pattern pays feature extraction + materialization once
    (the losers wake up into a cache hit). Keys for distinct patterns never
    contend, and idle keys hold no memory."""

    def __init__(self):
        self._mu = threading.Lock()
        self._locks: dict = {}      # key -> [lock, refcount]

    @contextlib.contextmanager
    def lock(self, key):
        with self._mu:
            ent = self._locks.get(key)
            if ent is None:
                ent = [threading.Lock(), 0]
                self._locks[key] = ent
            ent[1] += 1
        ent[0].acquire()
        try:
            yield
        finally:
            ent[0].release()
            with self._mu:
                ent[1] -= 1
                if ent[1] == 0:
                    self._locks.pop(key, None)


def _apply_plan_perm(a: HostCSR, plan: Plan, *, symmetric: bool) -> HostCSR:
    if plan.perm is None:
        return a
    if symmetric and a.nrows == a.ncols:
        return a.permute_symmetric(plan.perm)
    return a.permute_rows(plan.perm)


# ---------------------------------------------------------------------------
# chained products: the association and the sparse product's carry
# ---------------------------------------------------------------------------

# a chain hop the XLA schemes would serve through a dense C larger than
# this (rows × columns × 4 B) is planned on the Pallas candidates, whose
# sparse-C route returns C sparse
_CHAIN_DENSE_C_BUDGET = 256 * 2**20

# the largest dense C (rows × columns × 4 B) a masked product may be read
# from when it cannot run on the sparse-C tier: the dense product lives
# on the device beside the operands and again on the host, and a masked
# product of a graph's size would not fit (n = 65,536 is 16 GiB)
_MASKED_DENSE_C_BUDGET = 2**30


def chain_order(shapes: Sequence[tuple], nnzs: Sequence[int]):
    """The association of the product ``M₀·M₁·…·M_m`` from the operands'
    shapes and nnz alone: a binary tree of operand indices, e.g.
    ``(0, (1, 2))`` for ``M₀·(M₁·M₂)``.

    Orders are ranked by, in turn: the number of products (every
    intermediate and the result) wider than the sparse-C tier's C row
    strip (:func:`repro.kernels.ops.compact_grid_ok_ncols`); the
    estimated flops, ``2·nnz(X)·nnz(Y)/rows(Y)`` a product, with
    ``nnz(X·Y) ≈ min(flops/2, rows·cols)``; the intermediates' summed
    column counts; and last the left-most split, so ``A·A·A`` stays
    ``(A·A)·A``.

    >>> chain_order([(4, 8), (8, 8), (8, 4)], [8, 24, 8])
    (0, (1, 2))
    >>> chain_order([(8, 8)] * 3, [24] * 3)
    ((0, 1), 2)
    """
    from fractions import Fraction
    m = len(shapes)
    # best[(i, j)] = (rank, tree, est. nnz) of the product M_i … M_j
    best: dict = {(i, i): ((0, Fraction(0), 0), i, Fraction(nnzs[i]))
                  for i in range(m)}
    for span in range(1, m):
        for i in range(m - span):
            j = i + span
            rows, cols = shapes[i][0], shapes[j][1]
            wide = 0 if kernel_ops.compact_grid_ok_ncols(cols) else 1
            inner = cols if (i, j) != (0, m - 1) else 0
            cands = []
            for s in range(j - 1, i - 1, -1):      # left-most split last
                (wl, fl, cl), tl, nl = best[(i, s)]
                (wr, fr, cr), tr, nr = best[(s + 1, j)]
                flops = 2 * nl * nr / max(shapes[s + 1][0], 1)
                rank = (wl + wr + wide, fl + fr + flops, cl + cr + inner)
                cands.append((rank, (tl, tr),
                              min(flops / 2, Fraction(rows * cols))))
            best[(i, j)] = min(cands, key=lambda c: c[0])
    return best[(0, m - 1)][1]


def _chain_steps(tree, m: int) -> list[tuple[int, int]]:
    """The hops of an association tree in the order they run, each
    ``(left, right)`` indexing the operands ``0 … m-1`` and then the
    hops' results ``m, m+1, …``."""
    steps: list[tuple[int, int]] = []

    def walk(t) -> int:
        if isinstance(t, int):
            return t
        left, right = walk(t[0]), walk(t[1])
        steps.append((left, right))
        return m + len(steps) - 1
    walk(tree)
    return steps


@jax.jit
def _take(slabs, positions):
    return slabs.reshape(-1)[positions]


@jax.jit
def _masked_take(slabs, positions, mask_values):
    return slabs.reshape(-1)[positions] * mask_values


@dataclasses.dataclass(frozen=True)
class _ProductPattern:
    """The symbolic pattern of a sparse-C hop's product, in the original
    row/column order, and where each of its nonzeros sits in the
    :class:`repro.core.formats.CompactedC` slabs: the same for every
    value set on the hop's operand patterns."""

    indptr: np.ndarray
    indices: np.ndarray
    shape: tuple
    positions: jax.Array           # int32, into the flattened slabs

    @classmethod
    def of(cls, pattern, a: HostCSR, b: Optional[HostCSR],
           perm: Optional[np.ndarray]) -> "_ProductPattern":
        """Run the packed ``pattern`` once on unit values (a sum of
        products of ones is zero only off the symbolic pattern) and read
        the nonzeros' slab positions back; ``b=None`` is the squared
        product under a symmetric ``perm``."""
        ones = pattern.fill(np.ones(a.nnz, np.float32),
                            None if b is None else np.ones(b.nnz,
                                                           np.float32))
        cc = pattern.run_sparse(*ones)
        table, slabs = to_host(cc.table, cc.slabs)
        table = table.reshape(cc.nblocks, cc.nnb)
        blk, j = np.nonzero(table > 0)
        slab = table[blk, j].astype(np.int64)
        w, rr, col = np.nonzero(slabs[slab])
        rows = blk[w].astype(np.int64) * cc.block_r + rr
        cols = j[w].astype(np.int64) * cc.bn + col
        pos = (slab[w] * cc.block_r + rr) * cc.bn + col
        if perm is not None:
            p = np.asarray(perm, dtype=np.int64)
            rows = p[rows]
            if b is None:
                cols = p[cols]
        nrows, ncols = a.nrows, (a if b is None else b).ncols
        order = np.lexsort((cols, rows))
        indptr = np.zeros(nrows + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=nrows), out=indptr[1:])
        positions, = to_device(kernel_ops.int32_positions(pos[order]))
        return cls(indptr, cols[order].astype(np.int32), (nrows, ncols),
                   positions)

    @classmethod
    def of_mask(cls, pattern, mask: HostCSR) -> "_ProductPattern":
        """The masked product's pattern: the mask's own, in the order it
        was sent, at the slab positions the pack recorded for it
        (``SpGEMMPattern.mask_pos``); nothing runs."""
        return cls(np.array(mask.indptr), np.array(mask.indices),
                   mask.shape, pattern.mask_pos)

    def take(self, cc, mask_values: Optional[jax.Array] = None
             ) -> jax.Array:
        """The product's nonzeros from its slabs, on the device; times
        ``mask_values`` entry by entry when given (one jitted gather and
        multiply)."""
        if mask_values is None:
            return _take(cc.slabs, self.positions)
        return _masked_take(cc.slabs, self.positions, mask_values)


class _DeviceCSR:
    """A sparse-C hop's product still on the device: its nonzeros'
    values, in the order of its :class:`_ProductPattern`."""

    def __init__(self, values: jax.Array, product: _ProductPattern):
        self.values, self.product = values, product
        self.shape = product.shape

    @property
    def nbytes(self) -> int:
        return device_nbytes(self.values)

    def to_host(self) -> HostCSR:
        """The copy to the host (a ``fetch`` span) as a
        :class:`HostCSR`."""
        data, = to_host(self.values)
        p = self.product
        return HostCSR(p.indptr, p.indices, data, p.shape)


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


class Planner:
    """Feature-driven plan selection with a fingerprint-keyed cache.

    Args:
      cache: a :class:`PlanCache` (defaults to in-memory only — pass
        ``PlanCache(path=...)`` for an on-disk tier).
      cost_model: shared :class:`CostModel`; measurements accumulate here.
      measurer: ``(a, candidate) -> Measurement`` used by measured mode.
        Defaults to a direct on-device timing of the candidate. Benchmarks
        inject a measurer that reads the benchlib sweep cache instead.
      measure_top: how many shortlisted candidates measured mode probes.
      calibration: optional fitted
        :class:`~repro.planner.calibration.Calibration` forwarded into a
        default-constructed cost model (ignored when ``cost_model`` is
        given — configure that instance directly).
      pallas_b_dtype: dtype the pallas scheme packs B's live tiles in.
        ``None`` keeps fp32 (bit-compatible with the XLA paths);
        ``jnp.bfloat16`` halves B's streamed bytes at the documented
        looser parity tolerance (fp32 accumulation either way).
      auditor: drift auditor executed plans are recorded into (predicted
        score vs measured wall time — see :mod:`repro.obs.audit`).
        Defaults to the process-global auditor.
      resilience: the :class:`~repro.resilience.policy.ResiliencePolicy`
        arming the degradation ladder, output finiteness guard and
        circuit-breaker quarantine around :meth:`execute` / :meth:`plan`.
        ``None`` (default) resolves the process-global policy at use
        time; pass ``ResiliencePolicy.disabled()`` for the raw path.
      probe_timeout_s: hard per-candidate wall-clock cap on measured-mode
        probes — a candidate that exceeds it is skipped (scored
        heuristically) instead of wedging the request. ``None`` disables
        the cap.
      hint_provider: optional ``fingerprint -> int`` resolving the reuse
        hint when a caller passes ``reuse_hint=None`` — the serving
        front-end injects its live arrival-rate estimator here so the
        break-even rule sees measured recurrence instead of a static
        default. ``None`` (default) keeps ``reuse_hint=None`` meaning 1.
    """

    def __init__(self, cache: Optional[PlanCache] = None,
                 cost_model: Optional[CostModel] = None,
                 measurer: Optional[Callable[[HostCSR, Candidate],
                                             Measurement]] = None,
                 measure_top: int = 4,
                 measure_budget: float = 1.3,
                 candidates: Sequence[Candidate] = DEFAULT_CANDIDATES,
                 calibration=None,
                 pallas_b_dtype=None,
                 auditor: Optional[obs_audit.DriftAuditor] = None,
                 resilience: Optional[ResiliencePolicy] = None,
                 probe_timeout_s: Optional[float] = 30.0,
                 hint_provider: Optional[Callable[[str], int]] = None):
        self.cache = cache if cache is not None else PlanCache()
        self.auditor = (auditor if auditor is not None
                        else obs_audit.get_auditor())
        self.cost_model = (cost_model if cost_model is not None
                           else CostModel(calibration=calibration))
        self.pallas_b_dtype = (pallas_b_dtype if pallas_b_dtype is not None
                               else jnp.float32)
        self.measurer = measurer if measurer is not None else self._measure
        self.measure_top = measure_top
        self.measure_budget = measure_budget
        self.candidates = tuple(candidates)
        self._resilience = resilience
        self.probe_timeout_s = probe_timeout_s
        self.hint_provider = hint_provider
        self.probe_skips = 0
        # (fingerprint, candidate.key) -> materialization artifacts, so a
        # measured candidate's preprocessing is never run twice
        self._artifacts: dict[tuple[str, str], tuple] = {}
        # fingerprint -> {reorder: (matrix, perm)} shared across one
        # planning pass's probes (dropped with the artifacts)
        self._reorders: dict[str, dict] = {}
        # (plan key, value digest) -> packed device operands for execute()
        self._exec_cache: dict[str, tuple] = {}
        self._exec_cache_cap = 64
        # concurrent plans of one (fingerprint, workload) serialize so a
        # burst on a cold pattern preprocesses once, not once per request
        self._plan_flight = _SingleFlight()

    @property
    def resilience(self) -> ResiliencePolicy:
        """The effective policy: the injected one, else the process-global
        (resolved per use so tests swapping the global take effect)."""
        return (self._resilience if self._resilience is not None
                else get_policy())

    # -- planning ------------------------------------------------------------

    def plan(self, a: HostCSR, reuse_hint: Optional[int] = 1, *,
             measure: bool = False,
             candidates: Optional[Sequence[Candidate]] = None,
             use_cache: bool = True, workload: str = "a2",
             out_cols: Optional[int] = None) -> Plan:
        """Choose and materialize a (reorder, scheme) plan for ``a``.

        The do-nothing identity plan (original order, row-wise) is the
        implicit fallback whenever no candidate amortizes, even when it
        is not in ``candidates``.

        ``reuse_hint=None`` defers to the injected ``hint_provider``
        (the serving front-end's live arrival-rate estimator) when one is
        set, else 1. Concurrent calls on one (fingerprint, workload)
        single-flight: the first pays planning, the rest wake into the
        cached plan.

        ``workload`` selects the kernel family the plan is scored (and in
        measured mode, probed) on: ``"a2"`` — the paper's sparse×sparse
        product; ``"spmm"`` — the square × tall-skinny dense-B workload
        (measurements then run ``spmm_rowwise`` / ``spmm_clusterwise`` /
        ``cluster_spmm_compact``, not A² proxies); ``"chain"`` — one hop
        of a chained sparse product (A²-shaped per hop, probed as A²,
        but executed through :meth:`execute_chain`'s sparse-C route when
        the pallas scheme wins); ``"batch"`` — a block-diagonal pack of
        several requests' operands (A²-shaped, scored with the same
        per-core pallas discount, executed once through
        :meth:`execute_batch`). Cache entries are workload-keyed, so
        the workloads never shadow each other — a pack whose pattern
        collides with a single request's fingerprint still plans apart.
        """
        fp = fingerprint(a)
        if reuse_hint is None:
            reuse_hint = (self.hint_provider(fp)
                          if self.hint_provider is not None else 1)
        with get_tracer().span("plan", workload=workload,
                               measure=measure) as sp:
            with self._plan_flight.lock((fp, workload)):
                plan = self._plan_impl(a, reuse_hint, fp=fp,
                                       measure=measure,
                                       candidates=candidates,
                                       use_cache=use_cache,
                                       workload=workload,
                                       out_cols=out_cols)
            sp.set(fingerprint=plan.fingerprint, scheme=plan.scheme,
                   reorder=plan.reorder, cache_hit=plan.from_cache)
        reg = obs_metrics.get_registry()
        reg.counter("plan_total").inc()
        cs = self.cache.stats
        for key in ("hits", "misses", "evictions", "entries", "bytes"):
            reg.gauge(f"plan_cache_{key}").set(cs[key])
        policy = self.resilience
        if policy.ladder:
            reg.gauge("quarantine").set(len(policy.breaker.open_keys()))
        return plan

    def _plan_impl(self, a: HostCSR, reuse_hint: int, *, fp: str,
                   measure: bool,
                   candidates: Optional[Sequence[Candidate]],
                   use_cache: bool, workload: str,
                   out_cols: Optional[int] = None) -> Plan:
        """:meth:`plan` minus the span/metric/single-flight bookkeeping."""
        reuse_hint = max(int(reuse_hint), 1)
        if workload not in ("a2", "spmm", "chain", "batch"):
            raise ValueError(f"unknown workload '{workload}'")
        # workload-qualified key for cost-model measurements: an identity
        # baseline timed on SpMM must only normalize SpMM probes
        fp_w = fp if workload == "a2" else f"{fp}|{workload}"
        cands = tuple(candidates) if candidates is not None else self.candidates
        policy = self.resilience
        if use_cache:
            hit = self.cache.get(fp, reuse_hint, workload)
            if hit is not None:
                # a quarantined triple's cached plan is bypassed — NOT
                # evicted: when the breaker heals, the plan serves again
                # instantly. Until then we re-plan around it (and skip
                # the put below, preserving the cached entry).
                if not policy.allows(fp, hit.scheme, hit.reorder):
                    use_cache = False
                # a per-call candidate restriction must hold on hits too:
                # a cached plan outside the caller's set is replanned
                # fresh (without evicting the general cached plan)
                elif candidates is None or any(
                        c.reorder == hit.reorder and c.scheme == hit.scheme
                        for c in cands) or hit.is_identity:
                    return hit
                else:
                    use_cache = False
        if policy.ladder and policy.breaker.open_keys():
            # re-plan around quarantined (fingerprint, scheme, variant)
            # triples; identity stays the implicit fallback either way
            cands = tuple(c for c in cands
                          if policy.allows(fp, c.scheme, c.reorder))
        feats = extract_features(a)
        ranked = self.cost_model.rank(feats, reuse_hint, cands, fp_w,
                                      workload)
        if measure:
            with get_tracer().span("probe", fingerprint=fp,
                                   workload=workload):
                # the identity baseline normalizes every other measurement
                # — probe it even when the caller's candidate set omits it
                probes = [IDENTITY] + [sc.candidate
                                       for sc in self._shortlist(ranked)
                                       if sc.candidate.key != IDENTITY.key]
                for cand_p in probes:
                    if self.cost_model.measurement(fp_w,
                                                   cand_p) is not None:
                        continue
                    try:
                        m = self._call_measurer(a, cand_p, workload)
                    except ProbeTimeoutError:
                        # skip-and-score-heuristically: a pathological
                        # candidate must not wedge the request
                        self._note_probe_skip()
                        continue
                    self.cost_model.observe(fp_w, cand_p,
                                            m.kernel_s, m.preprocess_s)
            ranked = self.cost_model.rank(feats, reuse_hint, cands, fp_w,
                                          workload)
            # evidence only: an unmeasured candidate's optimistic heuristic
            # must not outrank the measured shortlist (identity is always
            # probed, so the pool is only empty when even the identity
            # probe hit the wall-clock cap — then the heuristic ranking
            # is all the evidence there is)
            pool = [s for s in ranked if s.measured] or ranked
        else:
            pool = ranked
        chosen = next((s for s in pool if s.amortizes), None)
        if (out_cols is not None
                and 4 * a.nrows * out_cols > _CHAIN_DENSE_C_BUDGET):
            chosen = next((s for s in pool
                           if s.candidate.scheme == "pallas"), chosen)
        if chosen is None:
            chosen = self.cost_model.score(feats, IDENTITY, reuse_hint,
                                           fp_w)

        cand = chosen.candidate
        art = self._artifacts.pop((fp_w, cand.key), None)
        if art is None:
            art = _materialize(a, cand,
                               reorder_cache=self._reorders.get(fp))
        perm, boundaries, max_cluster, t_pre = art
        plan = Plan(
            fingerprint=fp, reorder=cand.reorder, scheme=cand.scheme,
            reuse_hint=reuse_hint, max_cluster=max_cluster,
            workload=workload,
            perm=perm, boundaries=boundaries, preprocess_s=t_pre,
            predicted={
                "kernel_rel": chosen.kernel_rel,
                "preprocess_rel": chosen.preprocess_rel,
                "total_rel": chosen.total_rel,
                "break_even": (chosen.break_even
                               if np.isfinite(chosen.break_even) else -1.0),
                "measured": chosen.measured,
            },
            measured={
                s.candidate.key: {"kernel_rel": s.kernel_rel,
                                  "preprocess_rel": s.preprocess_rel}
                for s in ranked if s.measured
            })
        self._artifacts = {k: v for k, v in self._artifacts.items()
                           if k[0] != fp_w}        # drop losers' artifacts
        self._reorders.pop(fp, None)
        if use_cache:
            self.cache.put(plan)
        return plan

    def _call_measurer(self, a: HostCSR, cand: Candidate,
                       workload: str) -> Measurement:
        """Invoke the (possibly injected) measurer, passing ``workload``
        only when its signature takes one — pre-existing measurers keep
        their two-argument contract and probe the A² workload."""
        import inspect
        if getattr(self.measurer, "__func__", None) is Planner._measure:
            return self._measure(a, cand, workload=workload)
        try:
            takes_workload = "workload" in inspect.signature(
                self.measurer).parameters
        except (TypeError, ValueError):
            takes_workload = False
        if takes_workload:
            return self.measurer(a, cand, workload=workload)
        return self.measurer(a, cand)

    def _shortlist(self, ranked: list[ScoredCandidate]
                   ) -> list[ScoredCandidate]:
        """Identity (the baseline anchor) + the best amortizing candidates.

        Two gates keep probing cheap: non-amortizing candidates are never
        measured (the break-even rule), and the cumulative *predicted*
        preprocessing of the shortlist is capped at ``measure_budget``
        SpGEMM-equivalents — the planner must not spend more measuring
        than the plans it produces can save.
        """
        out = [s for s in ranked if s.candidate.key == IDENTITY.key]
        spent = 0.0
        for s in ranked:
            if len(out) >= self.measure_top:
                break
            if not s.amortizes or s.candidate.key == IDENTITY.key:
                continue
            if spent + s.preprocess_rel > self.measure_budget:
                continue
            spent += s.preprocess_rel
            out.append(s)
        return out

    # -- direct measurement (default measurer) -------------------------------

    def _measure(self, a: HostCSR, cand: Candidate, *,
                 reps: int = 2, workload: str = "a2") -> Measurement:
        """Time preprocessing + one-call kernel of ``cand`` on ``a``.

        Probes of one planning pass share materialized reorders (see
        ``_materialize``): the second scheme probed under the same reorder
        pays only its clustering increment.

        ``probe_timeout_s`` is a hard per-candidate wall-clock cap
        (materialize + compile/warm + timed reps): past the deadline with
        no timed rep yet, :class:`ProbeTimeoutError` tells the planning
        loop to skip the candidate; with at least one rep banked the
        measurement is simply cut short and returned.
        """
        t_start = time.perf_counter()
        cap = self.probe_timeout_s

        def _over() -> float | None:
            if cap is None:
                return None
            el = time.perf_counter() - t_start
            return el if el > cap else None

        fp = fingerprint(a)
        fp_w = fp if workload == "a2" else f"{fp}|{workload}"
        rcache = self._reorders.setdefault(fp, {})
        perm, boundaries, max_cluster, t_pre = _materialize(
            a, cand, reorder_cache=rcache)
        self._artifacts[(fp_w, cand.key)] = (perm, boundaries, max_cluster,
                                             t_pre)
        el = _over()
        if el is not None:
            raise ProbeTimeoutError(cand.key, el, cap)
        plan = Plan(fingerprint=fp, reorder=cand.reorder, scheme=cand.scheme,
                    reuse_hint=1, max_cluster=max_cluster, perm=perm,
                    boundaries=boundaries, workload=workload)
        # the spmm workload (and any rectangular matrix) probes the
        # tall-skinny dense-B kernels — spmm_rowwise / spmm_clusterwise /
        # cluster_spmm_compact — so execute(plan, a, dense_b) choices rest
        # on SpMM measurements, not A² proxies
        probe_b = None
        if workload == "spmm" or a.nrows != a.ncols:
            probe_b = np.asarray(
                np.random.default_rng(0).standard_normal((a.ncols, 32)),
                dtype=np.float32)
        runner = self._build_runner(plan, a, probe_b)
        runner()                                        # compile + warm
        el = _over()
        if el is not None:
            raise ProbeTimeoutError(cand.key, el, cap)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(runner())
            best = min(best, time.perf_counter() - t0)
            if _over() is not None:
                break                    # one rep banked: cut short, keep it
        return Measurement(kernel_s=best, preprocess_s=t_pre)

    # -- execution -----------------------------------------------------------

    def execute(self, plan: Plan, a: HostCSR,
                b: HostCSR | np.ndarray | None = None) -> np.ndarray:
        """Run the planned product; returns dense C in original order.

        ``b=None`` → A² (the paper workload). A second ``HostCSR`` → A·B
        with A row-permuted only. A dense array → tall-skinny SpMM.
        The packed device operands are cached per (plan, workload), so
        repeated calls — the whole point of planning — skip packing too.

        Every execution is device-synced (``jax.block_until_ready``) and
        its wall time fed to the drift auditor next to the plan's
        predicted score.

        With the resilience policy's ladder armed (the default), a
        failing execution — a raising kernel/pack path or a non-finite
        output — **degrades instead of erroring**: the request re-runs
        down the fallback ladder (pallas → fixed XLA clusterwise →
        rowwise identity, all on ``reorder="original"``), the incident is
        recorded, and the failing (fingerprint, scheme, variant) triple
        is quarantined by the circuit breaker so the *next* request
        re-plans around it. Only when every rung fails does
        :class:`~repro.resilience.errors.LadderExhaustedError` escape.
        """
        policy = self.resilience
        if not policy.ladder:
            return self._execute_impl(plan, a, b)
        key = policy.triple(plan.fingerprint, plan.scheme, plan.reorder)
        try:
            out = self._execute_impl(plan, a, b)
        except Exception as e:           # noqa: BLE001 — ladder catches all
            primary = e                  # outlives the except block
            policy.breaker.record_failure(key)
        else:
            policy.breaker.record_success(key)
            return out
        return self._run_ladder(plan, a, b, primary)

    def execute_batch(self, plan: Plan, a: HostCSR,
                      b: HostCSR | None = None) -> np.ndarray:
        """One block-diagonal batched launch — guarded, but **without**
        the fallback ladder.

        The ladder degrades a *single* request in place; re-running a
        whole batch down the rungs would make every co-batched tenant
        pay (repeatedly) for one member's fault, and the identity rung's
        fault suppression would mask *which* member carried it. So a
        failing batched launch is resolved one level up: the circuit
        breaker records the failing triple, the incident is recorded
        with ``fallback="unbatch"``, and the error propagates so the
        batcher disbands the group — each member then re-runs
        individually through :meth:`execute`'s full ladder, isolating
        the fault to the request that owns it.
        """
        policy = self.resilience
        if not policy.ladder:
            return self._execute_impl(plan, a, b)
        key = policy.triple(plan.fingerprint, plan.scheme, plan.reorder)
        try:
            out = self._execute_impl(plan, a, b)
        except Exception as e:           # noqa: BLE001 — batcher disbands
            policy.breaker.record_failure(key)
            policy.record_incident(
                fingerprint=plan.fingerprint, workload=plan.workload,
                scheme=plan.scheme, reorder=plan.reorder,
                site=self._classify_failure(e), error=e,
                fallback="unbatch")
            obs_metrics.get_registry().counter(
                "serve_fallbacks", scheme=plan.scheme).inc()
            raise
        policy.breaker.record_success(key)
        return out

    def _run_ladder(self, plan: Plan, a: HostCSR,
                    b: HostCSR | np.ndarray | None,
                    primary: Exception) -> np.ndarray:
        """Walk the fallback rungs below ``plan.scheme`` after ``primary``
        failed; records the incident and the ``serve_fallbacks`` metric
        on the rung that recovers the request."""
        policy = self.resilience
        tracer = get_tracer()
        site = self._classify_failure(primary)
        causes: list[tuple[str, Exception]] = [(plan.scheme, primary)]
        for rung in fallback_chain(plan.scheme):
            fb = self._fallback_plan(plan, rung, a)
            with tracer.span("fallback", fingerprint=plan.fingerprint,
                             from_scheme=plan.scheme, to_scheme=rung,
                             site=site) as sp:
                try:
                    if rung == "rowwise":
                        # the identity rung is the guaranteed-safe floor:
                        # in production nothing is armed; under the chaos
                        # harness it runs fault-suppressed
                        with _faults.suppressed():
                            out = self._execute_impl(fb, a, b)
                    else:
                        out = self._execute_impl(fb, a, b)
                except Exception as e:   # noqa: BLE001 — ladder walks on
                    causes.append((rung, e))
                    sp.set(recovered=False)
                    continue
                sp.set(recovered=True)
            policy.record_incident(
                fingerprint=plan.fingerprint, workload=plan.workload,
                scheme=plan.scheme, reorder=plan.reorder, site=site,
                error=primary, fallback=rung)
            obs_metrics.get_registry().counter(
                "serve_fallbacks", scheme=plan.scheme).inc()
            return out
        policy.record_incident(
            fingerprint=plan.fingerprint, workload=plan.workload,
            scheme=plan.scheme, reorder=plan.reorder, site=site,
            error=primary, fallback="")
        raise LadderExhaustedError(plan.scheme, causes) from primary

    @staticmethod
    def _guard(plan: Plan, out) -> np.ndarray:
        """The output guard, timed as the ``guard`` span: the chaos
        harness's ``output`` site corrupts here, and non-finite results
        raise (a single ``np.sum`` reduction propagates any NaN/Inf)."""
        out = _faults.corrupt_output("output", out)
        with get_tracer().span("guard"):
            # np.asarray first: on a device array, np.sum would dispatch
            # a traced jax reduction that silently truncates the
            # requested float64 accumulator to f32 — the host-side f64
            # sum is both the intended overflow-safe accumulation and
            # cheaper
            if not np.isfinite(np.sum(np.asarray(out), dtype=np.float64)):
                raise NonFiniteOutputError(plan.scheme)
        return out

    @staticmethod
    def _classify_failure(e: Exception) -> str:
        if isinstance(e, NonFiniteOutputError):
            return "nonfinite"
        site = getattr(e, "site", None)     # FaultInjectedError carries it
        return site if isinstance(site, str) else "exception"

    def _fallback_plan(self, plan: Plan, rung: str, a: HostCSR) -> Plan:
        """A rung's plan: same fingerprint/workload, ``reorder="original"``
        (a failing request must not pay a reorder on its recovery path).
        The fixed rung's boundaries are an O(nrows) recompute; its packed
        operands exec-cache like any plan's, so repeated fallbacks on one
        operand pay host packing once."""
        if rung == "rowwise":
            return Plan(fingerprint=plan.fingerprint, reorder="original",
                        scheme="rowwise", reuse_hint=plan.reuse_hint,
                        max_cluster=plan.max_cluster,
                        workload=plan.workload)
        perm, boundaries, max_cluster, t_pre = _materialize(
            a, Candidate("original", rung), max_cluster=plan.max_cluster)
        return Plan(fingerprint=plan.fingerprint, reorder="original",
                    scheme=rung, reuse_hint=plan.reuse_hint,
                    max_cluster=max_cluster, workload=plan.workload,
                    perm=perm, boundaries=boundaries, preprocess_s=t_pre)

    def _execute_impl(self, plan: Plan, a: HostCSR,
                      b: HostCSR | np.ndarray | None = None) -> np.ndarray:
        """One execution, without the ladder's rungs. With the ladder
        off it is the raw path the overhead benchmark baselines against;
        with it armed, the output guard runs last, inside the
        ``execute`` span."""
        tracer = get_tracer()
        with tracer.span("execute", fingerprint=plan.fingerprint,
                         scheme=plan.scheme, reorder=plan.reorder,
                         workload=plan.workload) as sp:
            runner = self._build_runner(plan, a, b)
            with tracer.span("kernel", scheme=plan.scheme):
                t0 = time.perf_counter()
                out = runner()      # block_until_ready inside the runner
                kernel_s = time.perf_counter() - t0
            rec = self.auditor.record(plan, kernel_s)
            if tracer.enabled:
                sp.set(kernel_s=kernel_s)
                if rec is not None:
                    sp.set(predicted_rel=rec.predicted_rel,
                           measured_rel=rec.measured_rel,
                           residual=rec.residual)
            return (self._guard(plan, out) if self.resilience.ladder
                    else out)

    # -- chained products (workload="chain") ---------------------------------

    def execute_chain(self, a: HostCSR,
                      operands: Optional[Sequence[HostCSR]] = None, *,
                      hops: Optional[int] = None,
                      reuse_hint: Optional[int] = None,
                      measure: bool = False,
                      candidates: Optional[Sequence[Candidate]] = None,
                      workload: str = "chain"
                      ) -> tuple[HostCSR, list[Plan]]:
        """Chained sparse product ``a · operands[0] · … · operands[-1]``
        of distinct, possibly rectangular operands; ``hops=k`` is the
        special case ``operands = (a,) * k``, ``A^(k+1)`` (``hops=2``,
        the default, is the A³ demo).

        The association comes from the operands' shapes and nnz alone
        (:func:`chain_order`): every intermediate narrow enough for the
        sparse-C tier where some order allows it, then the fewest
        estimated flops. ``R·A·P`` of AMG set-up runs as ``R·(A·P)``,
        ``A³`` as ``(A·A)·A``.

        Each hop is one Sp×Sp product: it plans its left operand under
        ``workload`` (``"chain"``; an operand chain served by
        :class:`repro.serve.engine.SpGEMMServer` plans under ``"a2"``,
        so that a plan cached for the operand's plain products serves
        the hop too). The plan cache keys on each operand's fingerprint,
        so a repeated chain hits the cache at every hop. Pallas-scheme
        hops run the sparse-C tier on a pattern-keyed exec entry (packed
        once per pattern, refilled on the device for new values, as
        :meth:`_pallas_runner`'s) and leave their product on the device;
        it comes back to the host as a :class:`HostCSR` of the hop's
        symbolic pattern, the same for every value set, under a
        ``carry`` span that ends after the next hop's refill. XLA-scheme
        hops densify and re-sparsify.

        Returns ``(C, plans)``: ``C`` a :class:`HostCSR` in the original
        row/column order, ``plans`` the per-hop plans, in the order the
        hops ran.
        """
        if operands is None:
            hops = 2 if hops is None else int(hops)
            if hops < 1:
                raise ValueError(f"hops must be >= 1, got {hops}")
            operands = (a,) * hops
        elif hops is not None:
            raise ValueError("give either operands or hops, not both")
        mats = [a, *operands]
        if len(mats) < 2:
            raise ValueError("a chain needs at least two operands")
        for x, y in zip(mats, mats[1:]):
            if x.ncols != y.nrows:
                raise ValueError(f"chain shapes do not match: {x.shape} · "
                                 f"{y.shape}")
        if reuse_hint is None and self.hint_provider is None:
            # each hop's plan serves one product per chain call; the
            # chain itself is the reuse unit, so default to expecting a
            # handful of repeated chains (the serving pattern). With a
            # hint provider injected, None flows through to plan() so
            # every hop's operand gets its own live estimate.
            reuse_hint = max(len(operands), 2)
        steps = _chain_steps(chain_order([m.shape for m in mats],
                                         [m.nnz for m in mats]), len(mats))
        vals: list = list(mats)           # operand, then each hop's result
        plans: list[Plan] = []
        tracer = get_tracer()
        reg = obs_metrics.get_registry()
        for k, (li, ri) in enumerate(steps):
            with tracer.span("hop", hop=k, hops=len(steps)) as sp:
                x, y = vals[li], vals[ri]
                plan = (None if isinstance(x, _DeviceCSR)
                        else self._plan_hop(x, y.shape[1], reuse_hint,
                                            measure, candidates, workload))
                with contextlib.ExitStack() as carry:
                    release = None
                    if isinstance(x, _DeviceCSR) or isinstance(y,
                                                               _DeviceCSR):
                        release = self._carry(carry, (x, y))
                    x, y = (v.to_host() if isinstance(v, _DeviceCSR)
                            else v for v in (x, y))
                    if plan is None:
                        plan = self._plan_hop(x, y.shape[1], reuse_hint,
                                              measure, candidates, workload)
                    plans.append(plan)
                    sp.set(fingerprint=plan.fingerprint, scheme=plan.scheme)
                    out = self._hop(plan, x, None if y is x else y,
                                    release=release)
                if k == len(steps) - 1 and isinstance(out, _DeviceCSR):
                    out = out.to_host()
                    self._check_finite(plan, out.data)
                vals.append(out)
            reg.counter("chain_hops").inc()
        return vals[-1], plans

    def _plan_hop(self, x: HostCSR, out_cols: int, reuse_hint, measure,
                  candidates, workload) -> Plan:
        t0 = time.perf_counter()
        plan = self.plan(x, reuse_hint, measure=measure,
                         candidates=candidates, workload=workload,
                         out_cols=out_cols)
        # per-hop planning wall time, annotated on the returned plan so
        # the serving layer can report a truthful plan_s for chain
        # requests (cache hits annotate ~0)
        plan.plan_wall_s = time.perf_counter() - t0
        return plan

    @staticmethod
    def _carry(stack: contextlib.ExitStack, pair) -> Callable:
        """Open the ``carry`` span of an intermediate's trip to the host
        and back on ``stack``; returns the ``release(filled)`` the next
        hop calls once its operands are on the device, which records the
        bytes moved (the intermediate's fetch, and its upload when the
        hop packed or refilled) and closes the span."""
        sp = stack.enter_context(get_tracer().span("carry"))
        moved = [v.nbytes for v in pair if isinstance(v, _DeviceCSR)]
        done = []

        def release(filled: bool) -> None:
            if done:
                return
            done.append(True)
            nbytes = sum(moved) * (2 if filled else 1)
            sp.set(bytes=nbytes)
            obs_metrics.get_registry().counter(
                "chain_carry_bytes").inc(nbytes)
            stack.close()
        return release

    def _check_finite(self, plan: Plan, data: np.ndarray) -> None:
        """The chain result's finiteness check, the ``guard`` span, while
        the resilience ladder is armed."""
        if not self.resilience.ladder:
            return
        with get_tracer().span("guard"):
            if not np.isfinite(np.sum(data, dtype=np.float64)):
                raise NonFiniteOutputError(plan.scheme)

    def _chain_hop(self, plan: Plan, cur: HostCSR,
                   b: Optional[HostCSR]) -> HostCSR:
        """One hop ``cur · (b if b is not None else cur)`` → HostCSR."""
        out = self._hop(plan, cur, b)
        return out.to_host() if isinstance(out, _DeviceCSR) else out

    def _hop(self, plan: Plan, cur: HostCSR, b: Optional[HostCSR], *,
             release: Optional[Callable] = None):
        """One hop ``cur · (b if b is not None else cur)``: a
        :class:`_DeviceCSR` from the sparse-C route, or a
        :class:`HostCSR` from the dense one. ``release`` (see
        :meth:`_carry`) is called once the operands are on the device.

        With the ladder armed, a failing sparse-C route degrades to the
        dense :meth:`execute` path (itself ladder-guarded), recording
        the incident and quarantining the triple like any execution
        failure — a chain request survives a pallas hop failure."""
        policy = self.resilience
        if plan.scheme == "pallas":
            try:
                dev = self._chain_hop_sparse(plan, cur, b, release)
            except Exception as e:       # noqa: BLE001 — ladder catches all
                if not policy.ladder:
                    raise
                policy.breaker.record_failure(policy.triple(
                    plan.fingerprint, plan.scheme, plan.reorder))
                policy.record_incident(
                    fingerprint=plan.fingerprint, workload=plan.workload,
                    scheme=plan.scheme, reorder=plan.reorder,
                    site=self._classify_failure(e), error=e,
                    fallback="dense_route")
                obs_metrics.get_registry().counter(
                    "serve_fallbacks", scheme=plan.scheme).inc()
                dev = None
            if dev is not None:
                return dev
        if release is not None:
            release(False)
        return HostCSR.from_dense(self.execute(plan, cur, b))

    def _chain_hop_sparse(self, plan: Plan, cur: HostCSR,
                          b: Optional[HostCSR],
                          release: Optional[Callable] = None
                          ) -> Optional[_DeviceCSR]:
        """The sparse-C route of a pallas chain hop, or ``None`` when the
        compacted grid does not apply (B too wide for a C row strip →
        dense fallback through :meth:`execute`). Its exec entry is
        pattern-keyed like :meth:`_pallas_runner`'s and also holds the
        product's :class:`_ProductPattern`; the product stays on the
        device."""
        if not kernel_ops.compact_grid_ok_ncols((cur if b is None
                                                 else b).ncols):
            return None
        entry, slot, filled = self._pattern_slot(plan, cur, b,
                                                 sparse_out=True)
        if release is not None:
            release(filled)
        _, pattern, _, product = entry
        _, values, tiled = slot
        tracer = get_tracer()
        with tracer.span("kernel", scheme=plan.scheme, variant="sparse_c"):
            t0 = time.perf_counter()
            vals = product.take(pattern.run_sparse(values, tiled))
            with tracer.span("sync"):
                vals = jax.block_until_ready(vals)
            kernel_s = time.perf_counter() - t0
        self.auditor.record(plan, kernel_s)
        return _DeviceCSR(vals, product)

    # -- masked products (submit(a, Masked(b, mask))) ------------------------

    def execute_masked(self, plan: Plan, a: HostCSR, b: Optional[HostCSR],
                       mask: HostCSR) -> HostCSR:
        """The masked product ``(a · (b or a)) ∘ mask``: the product's
        values at the nonzeros of ``mask`` times ``mask``'s values, as a
        :class:`HostCSR` on exactly ``mask``'s pattern (explicit zeros
        where no product term lands). ``plan`` is ``a``'s Sp×Sp plan; its
        permutation applies to the mask as to the product.

        A Pallas plan runs the sparse-C tier on an exec entry of its own
        (:meth:`_pattern_slot` with the mask): the pack keeps only the
        live pairs of C windows that hold a nonzero of the mask, the
        kernel writes only those windows, and one jitted op gathers the
        values at the mask's positions and multiplies them by its values
        on the device (the ``mask`` span); only ``nnz(mask)`` values come
        back. Other plans, a B too wide for the sparse-C grid, and — with
        the ladder armed — a failing sparse-C launch take the dense
        product of :meth:`execute` and read the mask's entries from it,
        where that dense C is within ``_MASKED_DENSE_C_BUDGET``; a wider
        one is refused (``ValueError``), or the launch's own error
        raised, rather than run out of memory.
        """
        policy = self.resilience
        dense_fits = 4 * mask.nrows * mask.ncols <= _MASKED_DENSE_C_BUDGET
        if plan.scheme == "pallas" and kernel_ops.compact_grid_ok_ncols(
                (a if b is None else b).ncols):
            try:
                return self._masked_sparse(plan, a, b, mask)
            except Exception as e:       # noqa: BLE001 — ladder catches all
                if not (policy.ladder and dense_fits):
                    raise
                policy.breaker.record_failure(policy.triple(
                    plan.fingerprint, plan.scheme, plan.reorder))
                policy.record_incident(
                    fingerprint=plan.fingerprint, workload=plan.workload,
                    scheme=plan.scheme, reorder=plan.reorder,
                    site=self._classify_failure(e), error=e,
                    fallback="dense_route")
                obs_metrics.get_registry().counter(
                    "serve_fallbacks", scheme=plan.scheme).inc()
        elif not dense_fits:
            raise ValueError(
                f"masked product of shape {mask.shape} off the sparse-C "
                f"tier would read a dense C of {4 * mask.nrows * mask.ncols}"
                f" B, over the {_MASKED_DENSE_C_BUDGET} B budget")
        dense = self.execute(plan, a, b)
        rows = np.repeat(np.arange(mask.nrows), mask.row_nnz())
        return HostCSR(mask.indptr, mask.indices,
                       dense[rows, mask.indices] * mask.data, mask.shape)

    def _masked_sparse(self, plan: Plan, a: HostCSR, b: Optional[HostCSR],
                       mask: HostCSR) -> HostCSR:
        """:meth:`execute_masked` on the sparse-C tier."""
        tracer = get_tracer()
        with tracer.span("execute", fingerprint=plan.fingerprint,
                         scheme=plan.scheme, reorder=plan.reorder,
                         workload="masked"):
            entry, slot, _ = self._pattern_slot(plan, a, b, mask=mask)
            pattern, product = entry[1], entry[3]
            _, values, tiled, mask_values = slot
            with tracer.span("kernel", scheme=plan.scheme,
                             variant="sparse_c"):
                t0 = time.perf_counter()
                cc = pattern.run_sparse(values, tiled)
                with tracer.span("sync"):
                    cc = jax.block_until_ready(cc)
                kernel_s = time.perf_counter() - t0
            self.auditor.record(plan, kernel_s)
            with tracer.span("mask"):
                vals = product.take(cc, mask_values)
                if tracer.enabled:
                    jax.block_until_ready(vals)
            out = _DeviceCSR(vals, product).to_host()
            self._check_finite(plan, out.data)
        return out

    def _build_runner(self, plan: Plan, a: HostCSR,
                      b: HostCSR | np.ndarray | None):
        dense_b = isinstance(b, np.ndarray) or (
            b is not None and not isinstance(b, HostCSR))
        squared = b is None
        if squared and a.nrows != a.ncols:
            raise ValueError("A² workload needs a square matrix")
        if plan.scheme == "pallas" and not dense_b:
            return self._pallas_runner(plan, a, b)
        # the plan fingerprint is value-independent by design; the packed
        # device operands are not — key them by the operand values (and
        # for a second sparse operand, its pattern too) AND by the plan's
        # layout (perm/boundaries), which can differ between plans
        # sharing a fingerprint
        vk = _value_digest(a) if squared or dense_b \
            else f"{_value_digest(a)}|{fingerprint(b)}|{_value_digest(b)}"
        ck = f"{plan.fingerprint}|{_plan_digest(plan)}" \
             f"|{'sq' if squared else 'ab'}" \
             f"|{'dense' if dense_b else 'csr'}|{vk}"
        cached = self._exec_cache.get(ck)

        # the O(nnz) permutes only run on a packing miss — a cache hit
        # goes straight to the packed kernel (the serving steady state)
        perm = plan.perm

        if dense_b:
            bd, = to_device(np.asarray(b, dtype=np.float32))
            if cached is None:
                with get_tracer().span("pack", fingerprint=plan.fingerprint,
                                       scheme=plan.scheme, kind="dense_b"):
                    _faults.maybe_fault("pack")
                    ap = _apply_plan_perm(a, plan, symmetric=False)
                    if plan.scheme == "rowwise":
                        dev = csr_from_host(ap)
                        cached = ("spmm_row", dev)
                    elif plan.scheme == "pallas":
                        # the compact stream, on the device: a hit
                        # uploads only X
                        cached = ("spmm_pallas",
                                  *kernel_ops.pack_spmm_stream(ap))
                    else:
                        cc = csr_cluster_from_host(
                            ap, self._bounds(plan, ap),
                            max_cluster=plan.max_cluster)
                        cached = ("spmm_cluster", cc)
                    self._exec_put(ck, cached)
                self._note_pack()
            else:
                self._note_hit(cached[0])
            kind = cached[0]
            if kind == "spmm_row":
                op = cached[1]
                out = lambda: spmm_rowwise(op, bd)         # noqa: E731
            elif kind == "spmm_pallas":
                _, shape, stream = cached
                out = lambda: kernel_ops.bcc_spmm_compact(  # noqa: E731
                    shape, bd, stream=stream)
            else:
                op = cached[1]
                out = lambda: spmm_clusterwise(op, bd)     # noqa: E731
            return self._unpermuted(out, perm, rows_only=True)

        if cached is None:
            with get_tracer().span("pack", fingerprint=plan.fingerprint,
                                   scheme=plan.scheme,
                                   kind="sq" if squared else "ab"):
                _faults.maybe_fault("pack")
                if squared:
                    ap = _apply_plan_perm(a, plan, symmetric=True)
                    bh = ap
                else:
                    ap = _apply_plan_perm(a, plan, symmetric=False)
                    bh = b
                dev_b = csr_from_host(bh)
                b_lens = bh.row_nnz()
                if plan.scheme == "rowwise":
                    dev_a = csr_from_host(ap)
                    fetch = np.zeros(dev_a.nnz_cap, dtype=np.int64)
                    fetch[: ap.nnz] = b_lens[ap.indices.astype(np.int64)]
                    bins = length_bins(fetch, pad_sentinel=dev_a.nnz_cap)
                    srows = slot_rows_host(np.asarray(dev_a.indptr),
                                           dev_a.nnz_cap)
                    cached = ("row", dev_a, dev_b, bins, srows)
                else:
                    cc = csr_cluster_from_host(
                        ap, self._bounds(plan, ap),
                        max_cluster=plan.max_cluster)
                    total = int(np.asarray(cc.cluster_ptr)[-1])
                    slot_cols = np.asarray(cc.cols)[:total].astype(np.int64)
                    fetch = np.zeros(cc.slot_cap, dtype=np.int64)
                    fetch[:total] = np.where(
                        slot_cols < bh.nrows, b_lens[
                            np.clip(slot_cols, 0, bh.nrows - 1)], 0)
                    bins = length_bins(fetch, pad_sentinel=cc.slot_cap)
                    sclust = slot_rows_host(np.asarray(cc.cluster_ptr),
                                            cc.slot_cap)
                    cached = ("cluster", cc, dev_b, bins, sclust)
                self._exec_put(ck, cached)
            self._note_pack()
        else:
            self._note_hit(cached[0])
        kind = cached[0]
        if kind == "row":
            _, op_a, op_b, bins, srows = cached
            out = lambda: spgemm_rowwise_dense_binned(  # noqa: E731
                op_a, op_b, bins, srows)
        else:
            _, op_a, op_b, bins, sclust = cached
            out = lambda: spgemm_clusterwise_dense_binned(  # noqa: E731
                op_a, op_b, bins, sclust)
        return self._unpermuted(out, perm, rows_only=not squared)

    def _pallas_runner(self, plan: Plan, a: HostCSR, b: HostCSR | None):
        """The Pallas Sp×Sp tier, BCC(A) × TiledCSR(B) on the MXU, on the
        pattern-keyed exec entry of :meth:`_pattern_slot`."""
        entry, slot, _ = self._pattern_slot(plan, a, b)
        pattern, (_, values, tiled) = entry[1], slot
        return self._unpermuted(lambda: pattern.run(values, tiled),
                                plan.perm, rows_only=b is not None)

    def _pattern_slot(self, plan: Plan, a: HostCSR, b: HostCSR | None, *,
                      sparse_out: bool = False,
                      mask: Optional[HostCSR] = None) -> tuple:
        """The exec entry of the Pallas product ``a · (b or a)`` and the
        value slot holding ``a``'s and ``b``'s values on the device.

        The entry is keyed by the operands' patterns: the plan and B's
        fingerprint. It holds the :class:`SpGEMMPattern`, packed once
        (the adaptive k-tile height, the compact A stream's ids, the live
        pairs, the shard partition, all on the device), and one value
        slot ``(value digest, A's stream values, B's TiledCSR)``. A
        request whose values differ from the slot's refills both arrays
        on the device from the sent ``data`` (a ``pack`` span of
        ``kind="refill"``); the same values again go straight to the
        kernel. A request builds its own slot and launches on it, so
        concurrent value sets never mix. ``sparse_out`` packs for the
        sparse-C tier (a chain hop's entry, apart from the dense one) and
        adds the product's :class:`_ProductPattern`. A ``mask`` packs the
        masked product ``(a · b) ∘ mask`` (:meth:`execute_masked`) on an
        entry of its own, also keyed by the mask's fingerprint, whose
        product pattern is the mask's; its value slot also holds the
        mask's values on the device, the very array of ``a``'s values
        when the two are equal.

        Returns ``(entry, slot, filled)``, ``filled`` true when this call
        packed or refilled."""
        squared = b is None
        a_vd = _value_digest(a)
        if mask is not None:
            kind, pack_kind = "masked", "masked"
            tag = "masked|" + (plan.fingerprint if mask is a
                               else fingerprint(mask)) + "|"
        elif sparse_out:
            kind, pack_kind, tag = "chain", "sparse_c", "chain|"
        else:
            kind, pack_kind, tag = "pallas", "sq" if squared else "ab", ""
        ck = (f"{plan.fingerprint}|{_plan_digest(plan)}|{tag}"
              + ("sq" if squared else f"ab|{fingerprint(b)}"))
        vk = a_vd if squared else f"{a_vd}|{_value_digest(b)}"
        if mask is not None:
            m_vd = a_vd if mask is a else _value_digest(mask)
            vk = f"{vk}|m{m_vd}"
        b_data = None if squared else b.data
        tracer = get_tracer()

        def fill(pattern) -> tuple:
            if mask is None:
                return (vk, *pattern.fill(a.data, b_data))
            if m_vd == a_vd:               # one upload serves both
                a_dev, = to_device(a.data)
                return (vk, *pattern.fill(a_dev, b_data), a_dev)
            return (vk, *pattern.fill(a.data, b_data),
                    *to_device(mask.data))

        entry = self._exec_cache.get(ck)
        if entry is None:
            with tracer.span("pack", fingerprint=plan.fingerprint,
                             scheme=plan.scheme, kind=pack_kind):
                _faults.maybe_fault("pack")
                ap, src = a, None
                mp, msrc = mask, None
                if plan.perm is not None:
                    ap, src = a.permuted(plan.perm, symmetric=squared)
                    if mask is not None:
                        mp, msrc = mask.permuted(plan.perm,
                                                 symmetric=squared)
                bh = ap if squared else b
                pattern = kernel_ops.pack_spgemm_pattern(
                    ap, bh, block_k=select_block_k(bh), a_src=src,
                    b_src=src if squared else None,
                    b_dtype=self.pallas_b_dtype, sparse_out=sparse_out,
                    mask=mp, mask_src=msrc)
                # a list: the value slot is replaced in place
                entry = [kind, pattern, None]
                if mask is not None:
                    entry.append(_ProductPattern.of_mask(pattern, mask))
                elif sparse_out:
                    entry.append(_ProductPattern.of(
                        pattern, a, None if squared else b, plan.perm))
                slot = fill(pattern)
                entry[2] = slot
                self._exec_put(ck, entry)
            self._note_pack()
            return entry, slot, True
        pattern, slot = entry[1], entry[2]
        if slot[0] == vk:
            self._note_hit(entry[0])
            return entry, slot, False
        # the old values leave the device before the new ones arrive
        entry[2] = (None,) * len(slot)
        with tracer.span("pack", fingerprint=plan.fingerprint,
                         scheme=plan.scheme, kind="refill"):
            slot = fill(pattern)
        entry[2] = slot
        obs_metrics.get_registry().counter("exec_cache_refills").inc()
        return entry, slot, True

    def _exec_put(self, key: str, packed: tuple) -> None:
        while len(self._exec_cache) >= self._exec_cache_cap:
            self._exec_cache.pop(next(iter(self._exec_cache)))
        self._exec_cache[key] = packed

    def _note_pack(self) -> None:
        """Account one exec-cache packing miss in the metrics registry."""
        reg = obs_metrics.get_registry()
        reg.counter("exec_cache_packs").inc()
        reg.gauge("exec_cache_entries").set(len(self._exec_cache))

    @staticmethod
    def _note_hit(kind: str) -> None:
        """Account one launch on a cached exec entry, with no pack and no
        refill, labelled by the entry's kind."""
        obs_metrics.get_registry().counter("exec_cache_hits",
                                           kind=kind).inc()

    def _note_probe_skip(self) -> None:
        """Account one wall-clock-capped probe skip."""
        self.probe_skips += 1
        obs_metrics.get_registry().counter("probe_skips").inc()

    @staticmethod
    def _bounds(plan: Plan, ap: HostCSR) -> list[int]:
        if plan.boundaries is None:
            raise ValueError(f"plan scheme {plan.scheme} has no boundaries")
        return np.asarray(plan.boundaries, dtype=np.int64).tolist()

    @staticmethod
    def _unpermuted(run, perm: Optional[np.ndarray], *, rows_only: bool):
        # block_until_ready before np.asarray: the conversion would sync
        # anyway, but syncing explicitly makes every timed region around a
        # runner measure device completion, not dispatch (and it is a
        # no-op passthrough for host-side numpy results). The wait is the
        # ``sync`` span; the copy to the host and the unpermute scatter
        # the ``fetch`` span.
        p = None if perm is None else np.asarray(perm, dtype=np.int64)

        def wrapped():
            tracer = get_tracer()
            res = run()
            with tracer.span("sync"):
                res = jax.block_until_ready(res)
            with tracer.span("fetch") as sp:
                cp = np.asarray(res)
                if tracer.enabled:
                    sp.set(bytes=device_nbytes(res))
                if p is None:
                    return cp
                out = np.empty_like(cp)
                if rows_only:
                    out[p] = cp
                else:
                    out[np.ix_(p, p)] = cp
                return out
        return wrapped

    @property
    def stats(self) -> dict:
        return {**self.cache.stats, "exec_entries": len(self._exec_cache),
                "probe_skips": self.probe_skips,
                "resilience": self.resilience.stats}


# ---------------------------------------------------------------------------
# module-level convenience API (the issue's public surface)
# ---------------------------------------------------------------------------


_DEFAULT: Optional[Planner] = None


def default_planner() -> Planner:
    """The process-wide serving planner: plans persist across processes
    in ``experiments/plan_cache/`` (gitignored, versioned keys) under an
    LRU byte budget — the on-disk store no longer grows unboundedly.
    Construct ``Planner()`` directly for an in-memory-only instance."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Planner(cache=PlanCache(path=DEFAULT_CACHE_DIR,
                                           max_bytes=DEFAULT_MAX_BYTES))
    return _DEFAULT


def reset_default_planner() -> None:
    global _DEFAULT
    _DEFAULT = None


def plan_spgemm(a: HostCSR, reuse_hint: int = 1, *,
                measure: bool = False, **kwargs) -> Plan:
    """Plan an SpGEMM on ``a`` expected to be reused ``reuse_hint`` times."""
    return default_planner().plan(a, reuse_hint, measure=measure, **kwargs)


def execute(plan: Plan, a: HostCSR,
            b: HostCSR | np.ndarray | None = None) -> np.ndarray:
    """Execute a planned product (see :meth:`Planner.execute`)."""
    return default_planner().execute(plan, a, b)


def execute_chain(a: HostCSR, operands: Optional[Sequence[HostCSR]] = None,
                  **kwargs) -> tuple[HostCSR, list]:
    """Chained product ``a · operands[0] · …`` (``hops=k``: ``A^(k+1)``)
    via the default planner (see :meth:`Planner.execute_chain`)."""
    return default_planner().execute_chain(a, operands, **kwargs)
