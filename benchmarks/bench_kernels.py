"""TPU-kernel-facing benchmark (beyond paper): the Pallas Sp×Sp tier vs the
XLA gather/scatter tier, plus BCC cluster_spmm occupancy statistics.

Two tables:

``spgemm_pallas_vs_xla`` — the tentpole comparison, per quick/default-tier
matrix:

* **B-bytes-fetched per output flop** of each path, counted from the
  formats themselves (:func:`repro.core.spgemm.b_bytes_rowwise_binned` /
  :func:`b_bytes_tiled`): the XLA path re-fetches 8 B (index+value) per
  padded gather element per A nonzero; the tiled path streams each live
  dense ``(128, 128)`` B tile into VMEM once. The *routed* column picks
  the footprint-optimal path per matrix over the planner's pallas reorder
  menu (original/rcm) — the oracle the cost model's ``tile128_fill`` gate
  approximates — its geomean is the acceptance gate (≥ 1.2×).
* **compacted-grid counters** (the v2 kernels' acceptance gates): grid
  steps per MXU issue of the live-pair stream (≤ 1.1 — only per-block
  zero-init sentinels and tail pads separate them), and the A-slab bytes
  ratio of the PR-3 padded ``(nnb, S)`` grid over the compacted grid
  (≥ 2× — the padded grid DMAs one A slab per grid step, dead or not;
  the compacted grid fetches each slab once per stream step). The
  ``a_bytes_stream_legacy`` column keeps the PR-3-era accounting (one A
  fetch per stream step) alongside the per-grid-step truth — the old
  counter under-reported the padded grid's A traffic ``nnb``-fold.
* **bf16 tile store**: B bytes of the fp32 tile store over the bf16 one
  (≈ 2× — same live lattice, half the bytes per slot).
* **sharding counters** (ISSUE 5): the worst per-core live-pair
  imbalance of the 4-way contiguous-block-range partition over the ideal
  split (gate: ≤ 1.2, i.e. within 20% of ideal).
* **sparse-C output counters** (ISSUE 6): C bytes the dense row strips
  would write to HBM over the :class:`~repro.core.formats.CompactedC`
  live slabs' bytes, known structurally from the live-pair stream (the
  symbolic phase — no numeric product runs). ``c_bytes_ratio_gm`` gates
  ≥ 2× over the *sparse-routed* families only (predicted C window
  density ≤ the ``ops`` auto-select threshold); dense-output families
  route dense-strip and owe no reduction. The interpret parity check
  also runs the sparse-C kernel epilogue end-to-end
  (``CompactedC → HostCSR``) — same accumulation order as the
  dense-strip kernel, so the round trip reproduces its output bit for
  bit and its ``spgemm_reference`` error exactly.
* **padding occupancy**: fill of B's live tile lattice and the A-side BCC
  padding fraction — the two waste terms the cost model trades off.
* wall-clock Pallas-vs-XLA speedup on a TPU backend (interpret mode is
  correctness-only and orders of magnitude slow, so CPU runs validate one
  small matrix against ``spgemm_reference`` instead of timing).

``bcc_kernel_occupancy_and_vmem`` — the PR-1-era SpMM occupancy table
(padded-grid vs compact-stream waste, VMEM budget check), unchanged.

Standalone (CI-checkable off-TPU): ``make bench-kernels`` runs this module
directly with ``--gate``, asserting the counter-only acceptance thresholds
— the counters come from the formats, not wall-clocks, so the gate is
deterministic in tier-1 time budget.
"""
from __future__ import annotations

import argparse
import sys
import time
from unittest import mock

import jax.numpy as jnp
import numpy as np

from repro.benchlib import representative_subset, time_fn
from repro.core.clustering import hierarchical_clusters
from repro.core.formats import (COUNTER_UNITS, CompactedC, bcc_from_host,
                                compacted_c_counters, compacted_c_table,
                                compacted_c_to_host, csr_from_host,
                                live_pair_counters, partition_balance,
                                tiled_csr_from_host,
                                tiled_live_tiles)
from repro.core.reorder import reorder
from repro.core.spgemm import (b_bytes_rowwise_binned, b_bytes_tiled,
                               flops_spgemm, length_bins, slot_rows_host,
                               spgemm_reference, spgemm_rowwise_dense_binned,
                               symbolic_row_nnz)
from repro.core.suite import generate
from repro.kernels import ops

from benchmarks.common import geomean, print_csv, tier_specs

VMEM_BUDGET = 16 * 2**20
BLOCK_K, BN = 128, 128

# counter-only acceptance thresholds (--gate / make bench-kernels)
GATE_STEPS_PER_MXU = 1.1          # compacted grid: ≤ this, geomean
GATE_A_BYTES_RATIO = 2.0          # padded-grid A bytes / compacted, ≥
GATE_B_ROUTED_RATIO = 1.2         # routed B-traffic ratio vs XLA, ≥
GATE_BF16_RATIO = 1.9             # fp32 / bf16 B tile store bytes, ≥
GATE_SHARD_BALANCE = 1.2          # worst per-core live-pair imbalance
                                  # over the ideal split, ≤ (within 20%)
GATE_C_BYTES_RATIO = 2.0          # dense-strip / CompactedC C bytes
                                  # written, sparse-routed families, ≥
BENCH_SHARDS = 4                  # cores the balance gate partitions for


def _xla_b_bytes(a) -> int:
    lens = a.row_nnz()[a.indices]
    bins = length_bins(lens)
    return b_bytes_rowwise_binned(bins, int(lens.shape[0]))


def _tiled_candidates(a) -> dict[str, "np.ndarray"]:
    """The tiled path's reorder menu — exactly the planner's pallas
    candidates (DEFAULT_CANDIDATES: original, rcm), so the routed column
    below only counts traffic wins the serving path can actually ship."""
    return {"original": a, "rcm": reorder(a, "rcm")[0]}


def _spgemm_pallas_vs_xla(tier: str) -> dict:
    specs = tier_specs(tier)
    rows = []
    ratios_tiled, ratios_routed = [], []
    steps_per_mxu, a_ratios, bf16_ratios, balances = [], [], [], []
    c_ratios_sparse, c_densities = [], []
    smallest = None              # (nnz, HostCSR) for the parity check below
    for spec in specs:
        a = generate(spec)
        if smallest is None or a.nnz < smallest[0]:
            smallest = (a.nnz, a)
        fl = max(flops_spgemm(a, a), 1)
        xla_b = _xla_b_bytes(a)
        best_name, best_b, best_live, best_mat = None, None, None, None
        for name, ar in _tiled_candidates(a).items():
            live = tiled_live_tiles(ar, BLOCK_K, BN)
            tb = b_bytes_tiled(live, BLOCK_K, BN)
            if best_b is None or tb < best_b:
                best_name, best_b, best_live, best_mat = name, tb, live, ar
        # the serving layout: A's row blocks as high as the pack chose
        pattern = ops.pack_spgemm_pattern(best_mat, best_mat,
                                          block_k=BLOCK_K)
        pairs = pattern.pairs
        block_r = pattern.a.block_r
        bcc = bcc_from_host(best_mat, block_r=block_r, block_k=BLOCK_K)
        stream = ops.bcc_compact_stream(bcc, cover_all_blocks=True)
        tiled_b = tiled_csr_from_host(best_mat, BLOCK_K, BN)
        routed_b = min(xla_b, best_b)
        ratio_tiled = xla_b / max(best_b, 1)
        ratio_routed = xla_b / max(routed_b, 1)
        ratios_tiled.append(ratio_tiled)
        ratios_routed.append(ratio_routed)
        tile_fill = a.nnz / max(best_live * BLOCK_K * BN, 1)
        a_pad = 1 - a.nnz / max(stream[2].size, 1)
        # A-slab traffic: the padded (nnb, S) grid DMAs one slab per grid
        # step — dead pair or not. The pre-compaction counter charged one
        # fetch per *stream step* (a_bytes_stream_legacy), under-reporting
        # the padded grid's A traffic nnb-fold; both are reported, the
        # per-grid-step figure is what the compacted ratio gates on.
        slab_bytes = block_r * BLOCK_K * 4
        s_steps = int(stream[0].shape[0])
        padded_steps = tiled_b.nnb * s_steps
        a_bytes_padded = padded_steps * slab_bytes
        a_bytes_legacy = s_steps * slab_bytes
        cnt = live_pair_counters(pairs, block_r=block_r, block_k=BLOCK_K,
                                 bn=BN)
        a_ratio = a_bytes_padded / max(cnt["a_bytes"], 1)
        nblocks = (best_mat.nrows + block_r - 1) // block_r
        # multi-core partition: the sharded route's own pack (its row
        # blocks no taller than BENCH_SHARDS cores balance), contiguous
        # block ranges balanced by live-pair count — worst per-core load
        # over the ideal split
        with mock.patch.object(ops, "pallas_shard_count",
                               lambda: BENCH_SHARDS):
            sharded = ops.pack_spgemm_pattern(best_mat, best_mat,
                                              block_k=BLOCK_K)
        balance = partition_balance(sharded.shards[1])
        # sparse-C output tier (ISSUE 6): C-side traffic, known before
        # the numeric phase — the live-pair stream pins the CompactedC
        # table, which pins the slab bytes; a structural (zero-slab)
        # CompactedC carries the table through compacted_c_counters with
        # the exact structural nnz(C) supplied symbolically. Only the
        # sparse-routed families (density ≤ the ops auto-select
        # threshold) enter the ≥2× gate — dense-output families ship the
        # dense-strip path and owe no reduction.
        c_density = ops.predict_c_window_density(pairs, nblocks=nblocks,
                                                 nnb=tiled_b.nnb)
        c_table, c_live = compacted_c_table(pairs, nblocks=nblocks,
                                            nnb=tiled_b.nnb)
        c_struct = CompactedC(
            slabs=jnp.zeros((c_live + 1, block_r, BN), jnp.float32),
            table=c_table, nrows=best_mat.nrows, ncols=best_mat.ncols,
            block_r=block_r, bn=BN)
        c_cnt = compacted_c_counters(
            c_struct,
            c_nnz=int(symbolic_row_nnz(best_mat, best_mat).sum()))
        c_ratio = (c_cnt["c_bytes_dense"]
                   / max(c_cnt["c_bytes_sparse"], 1))
        c_sparse_routed = pattern.route == "sparse_c"
        # bf16 tile store: measured from the actually-packed stores (not
        # re-derived from the byte formula), so a regression in the bf16
        # packing plumbing shows up as a gate failure
        tiled_b16 = tiled_csr_from_host(best_mat, BLOCK_K, BN,
                                        dtype=jnp.bfloat16)
        bf16_ratio = (tiled_b.nbytes_tiles()
                      / max(tiled_b16.nbytes_tiles(), 1))
        row = {
            "matrix": spec.name,
            "xla_b_bytes_per_flop": xla_b / fl,
            "tiled_b_bytes_per_flop": best_b / fl,
            "tiled_reorder": best_name,
            "routed": "pallas" if best_b < xla_b else "xla",
            "ratio_tiled": ratio_tiled,
            "ratio_routed": ratio_routed,
            "b_tile_fill": tile_fill,
            "a_slab_pad_frac": a_pad,
            "gathers_xla": a.nnz,
            "grid_steps_padded": padded_steps,
            "grid_steps_compact": cnt["grid_steps"],
            "mxu_issues": cnt["mxu_issues"],
            "steps_per_mxu": cnt["steps_per_mxu"],
            "a_bytes_padded_grid": a_bytes_padded,
            "a_bytes_stream_legacy": a_bytes_legacy,
            "a_bytes_compact": cnt["a_bytes"],
            "a_bytes_ratio": a_ratio,
            "b_bytes_bf16_ratio": bf16_ratio,
            "b_tile_fetches": cnt["b_tile_fetches"],
            "b_tile_refetches": cnt["b_tile_refetches"],
            "shard_balance": balance,
            "c_window_density": c_density,
            "c_routed": "sparse" if c_sparse_routed else "dense",
            "c_bytes_ratio": c_ratio,
            **c_cnt,
        }
        steps_per_mxu.append(cnt["steps_per_mxu"])
        a_ratios.append(a_ratio)
        bf16_ratios.append(bf16_ratio)
        balances.append(balance)
        c_densities.append(c_density)
        if c_sparse_routed:
            c_ratios_sparse.append(c_ratio)
        if ops.on_tpu():
            # compiled wall-clock — only meaningful on the real MXU
            filled = pattern.fill(best_mat.data)
            t_pal = time_fn(lambda: pattern.run(*filled))
            dev = csr_from_host(a)
            bins = length_bins(a.row_nnz()[a.indices],
                               pad_sentinel=dev.nnz_cap)
            srows = slot_rows_host(np.asarray(dev.indptr), dev.nnz_cap)
            t_xla = time_fn(
                lambda: spgemm_rowwise_dense_binned(dev, dev, bins, srows))
            row["pallas_speedup"] = t_xla / max(t_pal, 1e-12)
        rows.append(row)
    # units discipline: every stream counter this table prints must be
    # declared (with its unit) in formats.COUNTER_UNITS — the same table
    # docs/kernels.md renders as the counters glossary
    undeclared = [k for k in {**cnt, **c_cnt} if k not in COUNTER_UNITS]
    assert not undeclared, f"counters missing units: {undeclared}"
    print_csv(rows, "spgemm_pallas_vs_xla_b_traffic")
    print("# counter units: counts are DMA/step events, *_bytes are HBM "
          "bytes — see repro.core.formats.COUNTER_UNITS (rendered in "
          "docs/kernels.md)")

    # interpret-mode parity check (CPU CI): one small matrix end-to-end —
    # fp32 compacted grid (bit-level vs reference tolerance) and the bf16
    # tile store (documented looser bound)
    sm = _principal_submatrix(smallest[1], 192)
    want = spgemm_reference(sm, sm)

    def product(**kw):
        pattern = ops.pack_spgemm_pattern(sm, sm, block_k=BLOCK_K, **kw)
        return np.asarray(pattern.run(*pattern.fill(sm.data)))
    t0 = time.perf_counter()
    got = product()
    t_interp = time.perf_counter() - t0
    err = float(np.abs(got - want).max())
    got16 = product(b_dtype=jnp.bfloat16)
    scale = max(float(np.abs(want).max()), 1e-9)
    err16 = float(np.abs(got16 - want).max()) / scale
    # sharded (serial partition): bit-identical to the unsharded
    # compacted grid by construction, so the parity bound is the same
    # 1e-4
    with mock.patch.object(ops, "pallas_shard_count", lambda: 2):
        got_sh = product()
    err_sh = float(np.abs(got_sh - want).max())
    # sparse-C kernel epilogue end-to-end: windowed-scatter compaction in
    # the kernel, CompactedC → HostCSR — same s-ascending fp32
    # accumulation per window as the dense-strip kernel, so the round
    # trip must reproduce its output bit for bit (and its reference
    # error exactly)
    pattern_sc = ops.pack_spgemm_pattern(sm, sm, block_k=BLOCK_K,
                                         sparse_out=True)
    cc_sm = ops._sparse_c_kernel(pattern_sc, *pattern_sc.fill(sm.data))
    got_sc = compacted_c_to_host(cc_sm).to_dense()
    assert np.array_equal(got_sc, got[:got_sc.shape[0], :got_sc.shape[1]]), \
        "sparse-C round trip diverged from the dense-strip kernel"
    err_sc = float(np.abs(got_sc - want).max())
    summary = {
        "b_bytes_ratio_tiled_gm": geomean(ratios_tiled),
        "b_bytes_ratio_routed_gm": geomean(ratios_routed),
        "routed_pallas_pct": 100.0 * sum(r["routed"] == "pallas"
                                         for r in rows) / max(len(rows), 1),
        "grid_steps_per_mxu_gm": geomean(steps_per_mxu),
        "a_bytes_ratio_compact_gm": geomean(a_ratios),
        "b_bytes_bf16_ratio_gm": geomean(bf16_ratios),
        "shard_balance_worst": max(balances) if balances else float("nan"),
        "c_bytes_ratio_gm": (geomean(c_ratios_sparse)
                             if c_ratios_sparse else float("nan")),
        "c_window_density_gm": geomean(c_densities),
        "c_sparse_routed_pct": (100.0 * len(c_ratios_sparse)
                                / max(len(rows), 1)),
        "interp_parity_max_err": err,
        "interp_parity_bf16_rel_err": err16,
        "interp_parity_sharded_max_err": err_sh,
        "interp_parity_sparse_c_max_err": err_sc,
        "interp_validate_s": t_interp,
    }
    if ops.on_tpu():
        sp = [r["pallas_speedup"] for r in rows if "pallas_speedup" in r]
        summary["pallas_wallclock_speedup_gm"] = geomean(sp)
    print_csv([summary], "spgemm_pallas_vs_xla_summary")
    return {"rows": rows, "summary": summary}


def _principal_submatrix(a, n: int):
    """Leading n×n principal submatrix (keeps interpret-mode validation
    grids small enough for CI)."""
    from repro.core.formats import HostCSR
    n = min(n, a.nrows)
    cut = int(a.indptr[n])
    keep = a.indices[:cut] < n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(
        np.repeat(np.arange(n), np.diff(a.indptr[:n + 1]))[keep],
        minlength=n), out=indptr[1:])
    return HostCSR(indptr, a.indices[:cut][keep], a.data[:cut][keep],
                   (n, n))


def _occupancy(tier: str) -> dict:
    n = 4 if tier == "quick" else 8
    specs = representative_subset(n)
    rows = []
    width = 128
    for spec in specs:
        a = generate(spec)
        # hierarchical clustering improves block density before packing
        hc = hierarchical_clusters(a)
        ar = a.permute_symmetric(hc.perm)
        bcc0 = bcc_from_host(a, block_r=8, block_k=128)
        bcc1 = bcc_from_host(ar, block_r=8, block_k=128)
        live0 = int(np.asarray(bcc0.ntiles).sum())
        live1 = int(np.asarray(bcc1.ntiles).sum())
        pad0 = 1 - live0 / bcc0.values.shape[0]
        pad1 = 1 - live1 / bcc1.values.shape[0]
        # VMEM per grid step: A slab + B tile + C tile (+ accum in f32)
        vmem = (8 * 128 + 128 * width + 8 * width) * 4
        b = jnp.asarray(np.random.default_rng(0).standard_normal(
            (a.ncols, width)), jnp.float32)
        t0 = time.perf_counter()
        ops.bcc_spmm_compact(bcc1, b, interpret=True)
        t_interp = time.perf_counter() - t0
        rows.append({
            "matrix": spec.name,
            "tiles_live_orig": live0,
            "tiles_live_hier": live1,
            "pad_frac_orig": pad0,
            "pad_frac_hier": pad1,
            "tile_reduction": 1 - live1 / max(live0, 1),
            "vmem_per_step_kib": vmem / 1024,
            "vmem_ok": vmem < VMEM_BUDGET,
            "interp_validate_s": t_interp,
        })
    print_csv(rows, "bcc_kernel_occupancy_and_vmem")
    return {"rows": rows}


def run(tier: str = "default") -> dict:
    spgemm = _spgemm_pallas_vs_xla(tier)
    occ = _occupancy(tier)
    return {"spgemm": spgemm["rows"], "summary": spgemm["summary"],
            "occupancy": occ["rows"]}


def check_gates(summary: dict) -> list[str]:
    """Counter-only acceptance gates — deterministic (no wall-clocks), so
    they hold off-TPU in interpret mode. Returns failure strings."""
    checks = [
        ("grid_steps_per_mxu_gm", "<=", GATE_STEPS_PER_MXU),
        ("a_bytes_ratio_compact_gm", ">=", GATE_A_BYTES_RATIO),
        ("b_bytes_ratio_routed_gm", ">=", GATE_B_ROUTED_RATIO),
        ("b_bytes_bf16_ratio_gm", ">=", GATE_BF16_RATIO),
        ("shard_balance_worst", "<=", GATE_SHARD_BALANCE),
        ("c_bytes_ratio_gm", ">=", GATE_C_BYTES_RATIO),
    ]
    fails = []
    for key, op, thr in checks:
        v = summary.get(key)
        if v is None or not np.isfinite(v):
            fails.append(f"{key}: missing")
        elif (v > thr) if op == "<=" else (v < thr):
            fails.append(f"{key}: {v:.4g} violates {op} {thr}")
    return fails


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tier", choices=["quick", "default", "full"],
                    default="quick")
    ap.add_argument("--gate", action="store_true",
                    help="fail on counter-gate violations (CI mode)")
    args = ap.parse_args()
    res = run(args.tier)
    if args.gate:
        fails = check_gates(res["summary"])
        if fails:
            for f in fails:
                print(f"# GATE FAIL {f}")
            sys.exit(1)
        print("# all kernel counter gates pass")


if __name__ == "__main__":
    main()
