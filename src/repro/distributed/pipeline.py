"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pipe``
mesh axis with ``shard_map`` + ``collective_permute``.

Completes the parallelism matrix (DP/TP/EP/SP/FSDP are pjit-native in
distributed/sharding.py; PP needs explicit scheduling, which SPMD
propagation cannot invent). Design:

* stage parameters are stacked on a leading axis sharded over ``pipe`` —
  inside the shard_map body each rank holds exactly its stage's weights;
* the schedule runs ``M + P - 1`` ticks; each tick shifts activations one
  rank to the right via ``jax.lax.ppermute`` and computes one microbatch on
  every rank in the active window (classic GPipe fill/steady/drain — the
  1F1B memory optimization applies on top of the same wiring for training;
  forward-only is what serving and this dry-run-facing module need);
* rank 0 feeds microbatch ``t`` at tick ``t``; rank ``P-1`` emits completed
  microbatch ``t`` at tick ``t + P - 1``. Bubble fraction = (P-1)/(M+P-1),
  reported by :func:`bubble_fraction` and asserted in tests.

The stage function must be shape-preserving ((B, ...) → (B, ...)), which
covers transformer blocks — the embedding/head live outside the pipe.

Sparse pipelines additionally route their per-stage operators through the
SpGEMM planner (:func:`plan_pipeline_stages` / :func:`pipeline_spmm_apply`):
a pipeline is the canonical amortization case — each stage's sparse matrix
multiplies *every* microbatch of *every* pass, so ``reuse_hint =
microbatches × passes`` and the planner picks per-stage schemes instead of
the pipeline hardcoding one.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.formats import HostCSR
from repro.obs import metrics as obs_metrics
from repro.obs.trace import get_tracer
from repro.planner.plan_cache import Plan
from repro.planner.service import Planner, default_planner

__all__ = ["pipeline_apply", "bubble_fraction", "plan_pipeline_stages",
           "pipeline_spmm_apply"]


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_stages + num_microbatches - 1)


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array, *,
                   mesh: Mesh, axis: str = "pipe") -> jax.Array:
    """Run ``x`` through ``P`` pipelined stages.

    Args:
      stage_fn: (params_for_one_stage, act (B, ...)) -> act (B, ...).
      stage_params: pytree whose leaves have leading dim P (= mesh[axis]).
      x: (M, B, ...) microbatched input (M = number of microbatches).

    Returns: (M, B, ...) output after all P stages in order.
    """
    nstages = mesh.shape[axis]
    m = x.shape[0]
    ticks = m + nstages - 1

    def body(params, xs):
        # params leaves: (1, ...) local stage slice; xs: (M, B, ...) [rank0's
        # copy is used; other ranks' xs are ignored by the schedule]
        local = jax.tree.map(lambda a: a[0], params)
        rank = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])                    # activation register
        outs = jnp.zeros((m, *xs.shape[1:]), xs.dtype)

        def tick(carry, t):
            buf, outs = carry
            # shift: every rank receives the previous rank's last output
            recv = jax.lax.ppermute(
                buf, axis, [(i, i + 1) for i in range(nstages - 1)])
            feed = jnp.where(t < m, xs[jnp.clip(t, 0, m - 1)],
                             jnp.zeros_like(recv))
            inp = jnp.where(rank == 0, feed, recv)
            out = stage_fn(local, inp)
            # last rank banks finished microbatch t-(P-1)
            slot = t - (nstages - 1)
            valid = (rank == nstages - 1) & (slot >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out.astype(o.dtype), jnp.maximum(slot, 0), 0),
                lambda o: o, outs)
            return (out, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(ticks))
        # every rank returns its `outs`; only the last rank's is real —
        # psum after masking gives all ranks the result (replicated out)
        mask = (rank == nstages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    in_specs = (pspec, P())           # x replicated; params pipe-sharded
    mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)
    return mapped(stage_params, x)


# ---------------------------------------------------------------------------
# planner-driven sparse pipeline stages
# ---------------------------------------------------------------------------


def plan_pipeline_stages(stage_mats: Sequence[HostCSR],
                         num_microbatches: int, *,
                         passes: int = 1,
                         planner: Optional[Planner] = None,
                         measure: bool = False) -> list[Plan]:
    """Plan every stage's sparse operator for pipelined reuse.

    Each stage matrix is applied to all ``num_microbatches × passes``
    microbatch activations, so that product is the stage's amortization
    budget — expensive preprocessing that a single call could never
    justify becomes worthwhile exactly when the pipeline is deep enough.
    Stages sharing a sparsity pattern hit the same cached plan. Defaults
    to the process-wide planner so plans and packed formats persist
    across calls; pass the same explicit planner to both this and
    :func:`pipeline_spmm_apply` to isolate them.
    """
    planner = planner if planner is not None else default_planner()
    reuse = max(num_microbatches * passes, 1)
    tracer = get_tracer()
    # pipeline stages apply sparse weights to dense activations — the
    # tall-skinny workload, so plans are scored (and in measured mode,
    # probed) on the SpMM kernel menu, not A² proxies
    plans = []
    for i, m in enumerate(stage_mats):
        with tracer.span("stage", stage=i, phase="plan") as sp:
            plan = planner.plan(m, reuse, measure=measure, workload="spmm")
            sp.set(scheme=plan.scheme, fingerprint=plan.fingerprint)
        plans.append(plan)
    return plans


def pipeline_spmm_apply(plans: Sequence[Plan],
                        stage_mats: Sequence[HostCSR],
                        x: np.ndarray, *,
                        planner: Optional[Planner] = None) -> np.ndarray:
    """Run microbatches through planned sparse stages (host orchestration).

    Args:
      plans: per-stage plans from :func:`plan_pipeline_stages`.
      stage_mats: per-stage square (F, F) ``HostCSR`` operators.
      x: (M, B, F) microbatched activations.

    Returns (M, B, F): each microbatch after ``y = A_s @ y`` for every
    stage ``s`` in order — the same schedule :func:`pipeline_apply` runs
    spatially, with each stage's scheme chosen by the planner instead of
    hardcoded. The packed per-stage formats live in the planner's execute
    cache (the process-wide planner by default), so all microbatches of
    all passes reuse one packing.
    """
    if len(plans) != len(stage_mats):
        raise ValueError("one plan per stage required")
    planner = planner if planner is not None else default_planner()
    m, bsz, feat = x.shape
    acts = np.asarray(x, dtype=np.float32)
    tracer = get_tracer()
    stage_hist = obs_metrics.get_registry().histogram("pipeline_stage_s")
    for i, (plan, mat) in enumerate(zip(plans, stage_mats)):
        if mat.nrows != mat.ncols or mat.ncols != feat:
            raise ValueError("stage matrices must be (F, F)")
        with tracer.span("stage", stage=i, phase="execute",
                         scheme=plan.scheme):
            t0 = time.perf_counter()
            # one (F, M·B) SpMM per stage: microbatches ride the dense
            # width; the planner runner device-syncs before returning
            flat = acts.reshape(m * bsz, feat).T        # (F, M·B)
            out = planner.execute(plan, mat, flat)      # (F, M·B)
            acts = out.T.reshape(m, bsz, feat)
            stage_hist.observe(time.perf_counter() - t0)
    return acts
