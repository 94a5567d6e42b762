"""Serving engine: batched prefill + decode with continuous batching slots,
plus the planner-driven SpGEMM serving front end.

``make_serve_step`` returns the jittable one-token step used by the dry-run
(``decode_*`` / ``long_*`` shapes). ``ServingEngine`` is the host-side loop:
fixed-size slot table, per-slot position tracking, greedy/temperature
sampling, slot recycling on EOS — the standard continuous-batching skeleton,
kept dependency-free.

``SpGEMMServer`` is the sparse-workload analogue: requests are (matrix,
operand, reuse hint) triples and the serving path no longer hardcodes one
reorder/cluster scheme — every pattern goes through
``repro.planner.plan_spgemm``, so the first request for a pattern pays
feature extraction + preprocessing once and every later request (same
fingerprint, any values) is a plan-cache hit straight into the packed
kernel.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import HostCSR, Masked
from repro.models.transformer import decode_step, init_cache, prefill
from repro.obs import metrics as obs_metrics
from repro.obs.trace import get_tracer
from repro.planner.service import Planner
from repro.resilience.errors import InvalidOperandError
from repro.resilience.validation import validate_request_pair

__all__ = ["make_serve_step", "ServingEngine", "SpGEMMServer", "Masked"]


def make_serve_step(cfg, *, sample: bool = False,
                    temperature: float = 1.0) -> Callable:
    """Returns f(params, cache, batch) -> (next_token_or_logits, cache)."""

    def serve_step(params, cache, batch, rng=None):
        logits, cache = decode_step(cfg, params, batch, cache)
        if not sample:
            return jnp.argmax(logits[:, -1], axis=-1), cache
        g = jax.random.gumbel(rng, logits[:, -1].shape)
        tok = jnp.argmax(logits[:, -1] / temperature + g, axis=-1)
        return tok, cache

    return serve_step


# ---------------------------------------------------------------------------
# planner-driven SpGEMM serving
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SpGEMMResponse:
    result: "np.ndarray | HostCSR"  # HostCSR for chain and masked requests
    fingerprint: str
    reorder: str
    scheme: str
    workload: str              # a2 | spmm | chain | masked
    kernel_path: str           # "pallas" (MXU tiled kernel) or "xla"
    plan_cache_hit: bool
    plan_s: float              # planning + preprocessing wall time (0-ish on hit)
    execute_s: float
    trace_id: str = ""         # root span's trace id ("" when tracing is off)
    degraded: bool = False     # served by a degradation-ladder rung
    fallback_scheme: str = ""  # the rung that recovered it ("" when not)
    coalesced: bool = False    # shared an identical in-flight execution
    downgraded: bool = False   # front-end forced the identity rung
    deadline_missed: bool = False  # completed past its deadline (counted)
    batched: bool = False      # served as one member of a block-diagonal
    batch_size: int = 0        # launch of this many distinct requests


class SpGEMMServer:
    """Serve repeated sparse products through the plan cache.

    One planner (one plan cache + one cost model) is shared across all
    requests; ``reuse_hint`` defaults to the server-level expectation of
    how often a pattern recurs in the traffic (per-request override wins).

    ``tenant`` names the traffic source this server fronts: when no
    planner is injected, the server's plan cache is namespaced to the
    tenant (``PlanCache(namespace=tenant)``), so its plans live — and are
    byte-budgeted — in their own partition and cannot be evicted by (or
    evict) another tenant's traffic, even when all servers share one
    on-disk cache directory.
    """

    def __init__(self, planner: Optional[Planner] = None, *,
                 default_reuse_hint: int = 20, measure: bool = False,
                 tenant: str = ""):
        if planner is None:
            from repro.planner.plan_cache import PlanCache
            planner = Planner(cache=PlanCache(namespace=tenant))
        self.planner = planner
        self.tenant = tenant
        self.default_reuse_hint = default_reuse_hint
        self.measure = measure
        self.requests = 0
        self.plan_hits = 0

    def submit(self, a: HostCSR,
               b: HostCSR | np.ndarray | tuple | Masked | None = None, *,
               reuse_hint: Optional[int] = None,
               hops: Optional[int] = None) -> SpGEMMResponse:
        """Plan (or fetch the cached plan for) ``a``, then execute a·b.

        A dense ``b`` routes the request through the planner's ``spmm``
        workload — its plan is scored (and measured) on the tall-skinny
        kernel menu, cached separately from the same pattern's A² plan.

        A tuple (or list) of :class:`HostCSR` as ``b`` asks for the
        chained product of distinct, possibly rectangular operands,
        ``a · b[0] · … · b[-1]`` — AMG's Galerkin product is
        ``submit(R, (A, P))``. ``hops=k`` is the special case
        ``b = (a,) * k``, ``A^(k+1)`` (``b`` must be ``None``). Both run
        under workload ``"chain"`` through
        :meth:`repro.planner.service.Planner.execute_chain`, which picks
        the association from the shapes; each hop of an operand chain is
        planned as the Sp×Sp product it is (``"a2"``), a power chain's
        under ``"chain"``. ``result`` is the sparse :class:`HostCSR`
        product, and the response reports the first hop's plan — with
        ``plan_cache_hit`` true only when *every* hop hit the cache (the
        steady serving state for a recurring chain) and ``kernel_path``
        ``"pallas"`` only when every hop was planned on it.

        :class:`~repro.core.formats.Masked` as ``b`` asks for the masked
        product ``(a · b.b) ∘ b.mask`` (``b.b=None``: ``a · a``), the
        kernel of triangle counting, under workload ``"masked"``: it is
        planned as the Sp×Sp product it is (``"a2"``) and run by
        :meth:`repro.planner.service.Planner.execute_masked`, which
        computes only the C windows the mask reads; ``result`` is a
        :class:`HostCSR` on exactly the mask's pattern.

        Each request runs under a ``request`` span, after a ``validate``
        span for the operand checks (its trace id is returned as
        ``SpGEMMResponse.trace_id`` when tracing is on; under the async
        front end both join the trace its ``admit`` span opened), and
        counts in the per-tenant ``serve_requests`` metric.

        With the resilience policy's validation armed (the default),
        malformed operands — a non-monotone ``indptr``, out-of-range or
        unsorted indices, non-finite data, an inconsistent shape chain —
        are rejected *here* with a structured
        :class:`~repro.resilience.errors.InvalidOperandError` instead of
        crashing deep inside a packed kernel; rejections count in the
        ``serve_rejects`` metric (labeled by the violated field). A
        request whose execution failed but was recovered by the
        degradation ladder reports ``degraded=True`` and the recovering
        rung in ``fallback_scheme``.
        """
        self.requests += 1
        if reuse_hint is not None:
            hint: Optional[int] = reuse_hint
        elif getattr(self.planner, "hint_provider", None) is not None:
            # the planner's injected live estimator resolves the hint
            # per fingerprint — the static default would override it
            hint = None
        else:
            hint = self.default_reuse_hint
        if hops is not None and b is not None:
            raise ValueError("chain requests take b=None (A^k workload)")
        workload = ("chain" if hops is not None
                    or isinstance(b, (tuple, list))
                    else "masked" if isinstance(b, Masked)
                    else "spmm" if (b is not None
                                    and not isinstance(b, HostCSR))
                    else "a2")
        reg = obs_metrics.get_registry()
        reg.counter("serve_requests", tenant=self.tenant).inc()
        policy = self.planner.resilience
        tracer = get_tracer()
        if policy.validate:
            with tracer.span("validate") as sp:
                if tracer.enabled:
                    sp.set(skipped_a=policy.is_validated(a))
                try:
                    validate_request_pair(a, b, skip=policy.is_validated)
                except InvalidOperandError as e:
                    policy.rejects += 1
                    reg.counter("serve_rejects", tenant=self.tenant,
                                field=e.field).inc()
                    raise
                policy.mark_validated(a)
                for m in (b if isinstance(b, (tuple, list))
                          else (b.b, b.mask) if isinstance(b, Masked)
                          else (b,)):
                    if hasattr(m, "indptr"):
                        policy.mark_validated(m)
        with tracer.span("request", tenant=self.tenant,
                         workload=workload) as root:
            resp = self._submit_impl(a, b, hint=hint, hops=hops,
                                     workload=workload)
            resp.trace_id = root.trace_id
            root.set(fingerprint=resp.fingerprint, scheme=resp.scheme,
                     cache_hit=resp.plan_cache_hit)
        return resp

    def _submit_impl(self, a: HostCSR, b, *, hint: Optional[int],
                     hops: Optional[int], workload: str) -> SpGEMMResponse:
        """:meth:`submit` minus the span/metric bookkeeping. Timed
        regions are device-synced: planner runners block until the device
        result is ready before the closing ``perf_counter`` read."""
        policy = self.planner.resilience
        inc0 = policy.fallbacks
        if workload == "chain":
            t0 = time.perf_counter()
            out, plans = self.planner.execute_chain(
                a, (a,) * hops if hops is not None else tuple(b),
                reuse_hint=hint, measure=self.measure,
                workload="chain" if hops is not None else "a2")
            t1 = time.perf_counter()
            hit = all(p.from_cache for p in plans)
            if hit:
                self.plan_hits += 1
            lead = plans[0]
            degraded = policy.fallbacks > inc0
            # truthful chain planning time: the sum of the per-hop
            # planning wall times execute_chain annotates on each plan
            plan_s = sum(getattr(p, "plan_wall_s", 0.0) for p in plans)
            return SpGEMMResponse(
                result=out, fingerprint=lead.fingerprint,
                reorder=lead.reorder, scheme=lead.scheme, workload="chain",
                kernel_path=("pallas" if all(p.scheme == "pallas"
                                             for p in plans) else "xla"),
                plan_cache_hit=hit, plan_s=plan_s,
                execute_s=max(t1 - t0 - plan_s, 0.0),
                degraded=degraded,
                fallback_scheme=(policy.incidents[-1].fallback
                                 if degraded else ""))
        t0 = time.perf_counter()
        plan = self.planner.plan(a, hint, measure=self.measure,
                                 workload="a2" if workload == "masked"
                                 else workload)
        t1 = time.perf_counter()
        if workload == "masked":
            out = self.planner.execute_masked(plan, a, b.b, b.mask)
        else:
            out = jax.block_until_ready(self.planner.execute(plan, a, b))
        t2 = time.perf_counter()
        if plan.from_cache:
            self.plan_hits += 1
        degraded = policy.fallbacks > inc0
        return SpGEMMResponse(
            result=out, fingerprint=plan.fingerprint, reorder=plan.reorder,
            scheme=plan.scheme, workload=workload,
            kernel_path="pallas" if plan.scheme == "pallas" else "xla",
            plan_cache_hit=plan.from_cache,
            plan_s=t1 - t0, execute_s=t2 - t1, degraded=degraded,
            fallback_scheme=(policy.incidents[-1].fallback
                             if degraded else ""))

    def stats(self) -> dict:
        """Serving snapshot: request/hit counts, the tenant's plan-cache
        partition (``PlanCache.stats``, both spread flat for
        back-compat and nested under ``"plan_cache"``) and the drift
        auditor's rolling summary under ``"audit"``, plus the resilience
        policy's fallback/reject/quarantine accounting under
        ``"resilience"``."""
        return {"requests": self.requests, "plan_hits": self.plan_hits,
                "tenant": self.tenant, **self.planner.stats,
                "plan_cache": dict(self.planner.cache.stats),
                "audit": self.planner.auditor.summary(),
                "resilience": self.planner.resilience.stats}


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # (len,) int32
    max_new_tokens: int = 32
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServingEngine:
    """Host-side continuous batching over a fixed slot table."""

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 512,
                 eos_id: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.cache = init_cache(cfg, slots, max_len,
                                dtype=jax.tree.leaves(params)[0].dtype)
        self.requests: list[Optional[Request]] = [None] * slots
        self.positions = np.zeros(slots, np.int64)
        self._step = jax.jit(make_serve_step(cfg))
        # one jitted replay step for the whole engine lifetime: tokens are
        # always (slots, 1) int32, so every prompt token of every request
        # reuses this single trace (constructing jax.jit(lambda ...)
        # inside the replay loop re-traced per token)
        self._replay_step = jax.jit(
            lambda p, c, b: decode_step(self.cfg, p, b, c))
        self._queue: list[Request] = []

    def submit(self, req: Request) -> None:
        self._queue.append(req)

    def _admit(self) -> None:
        for i in range(self.slots):
            if self.requests[i] is None and self._queue:
                req = self._queue.pop(0)
                self.requests[i] = req
                # replay prompt into this slot (per-slot decode replay keeps
                # the engine simple; bulk prefill exists for batch jobs)
                for t in req.prompt:
                    tok = jnp.zeros((self.slots, 1), jnp.int32)
                    tok = tok.at[i, 0].set(int(t))
                    _, self.cache = self._replay_step(
                        self.params, self.cache, {"tokens": tok})
                self.positions[i] = len(req.prompt)

    def run(self, steps: int) -> None:
        """NOTE: single shared `pos` keeps this demo engine simple; slots
        admitted together stay aligned. Per-slot positions would use a
        vector cache["pos"] — straightforward extension."""
        self._admit()
        for _ in range(steps):
            live = [i for i, r in enumerate(self.requests) if r is not None]
            if not live:
                return
            tok = jnp.zeros((self.slots, 1), jnp.int32)
            next_tok, self.cache = self._step(self.params, self.cache,
                                              {"tokens": tok})
            nt = np.asarray(next_tok)
            for i in live:
                req = self.requests[i]
                req.out.append(int(nt[i]))
                if (self.eos_id is not None and nt[i] == self.eos_id) \
                        or len(req.out) >= req.max_new_tokens:
                    req.done = True
                    self.requests[i] = None
            self._admit()
