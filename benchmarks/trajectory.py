"""Perf-trajectory artifacts: schema'd per-run summaries, diffable across PRs.

``benchmarks/run.py`` calls :func:`build_artifact` after a sweep and writes
``experiments/BENCH_<tier>_<git-sha>.json``. Tracked artifacts accumulate in
git (one per PR that ran the tier), so speedup/overhead trends are diffed
instead of recomputed — the ROADMAP's perf-trajectory item.

Schema (``repro-bench-trajectory/v1``)::

    {
      "schema": "repro-bench-trajectory/v1",
      "tier": "quick", "git_sha": "...", "kernel_gen": "v3",
      "created_unix": 1234567890,
      "tables": {
        "fig2":    {"geomean_speedup_by_reorder": {...}},
        "fig3":    {"geomean_speedup_by_scheme": {...}},
        "fig10":   {"preprocess_ratio_median": ..., "frac_under_20x": ...},
        "traffic": {"fetch_ratio_gm_by_scheme": {...}},
        "fig11":   {"memory_ratio_median_by_scheme": {...}},
        "planner": {"regret_gm": ..., "hier_over_planner_pre": ..., ...},
        ...
      }
    }

``python -m benchmarks.trajectory --tier quick --diff`` compares the two
newest artifacts of a tier and exits non-zero on a >10% geomean regression
(``make bench-trajectory`` runs the sweep then this gate).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np

SCHEMA = "repro-bench-trajectory/v1"
EXPERIMENTS_DIR = os.path.join(os.path.dirname(__file__), "..",
                               "experiments")
REGRESSION_THRESHOLD = 0.10

# metrics compared by the diff gate: (table, key-path, higher_is_better)
_GATED = [
    ("fig2", ("geomean_speedup_by_reorder",), True),
    ("fig3", ("geomean_speedup_by_scheme",), True),
    ("traffic", ("fetch_ratio_gm_by_scheme",), True),
    # preprocess is NOT gated: its engine-vs-reference host-timing ratios
    # drift with container conditions beyond any usable threshold — the
    # per-stage map drifts ±15-30% between sessions with byte-identical
    # code, and the cross-stage aggregate itself was measured at 8.44 in
    # one session and 5.97 in another *at the same commit* (verified by
    # re-running the baseline commit side by side). Both the per-stage
    # map and engine_speedup_gm_overall remain in the artifact for
    # inspection; regressions of the engine are caught by the
    # property-tested loop references and bench_preprocess itself.
    ("planner", ("hier_over_planner_pre",), True),
    ("planner", ("regret_gm",), False),
    # Pallas Sp×Sp tier: B traffic of the planner-routed path vs the XLA
    # gather path (and compiled wall-clock, present on TPU backends only)
    ("kernels", ("b_bytes_ratio_routed_gm",), True),
    ("kernels", ("pallas_wallclock_speedup_gm",), True),
    # compacted-grid counters (ISSUE 4): grid steps per MXU issue of the
    # live-pair stream (lower is better — sentinel/pad overhead only),
    # the padded-grid/compacted A-slab byte ratio and the fp32/bf16 B
    # tile store ratio (higher is better)
    ("kernels", ("grid_steps_per_mxu_gm",), False),
    ("kernels", ("a_bytes_ratio_compact_gm",), True),
    ("kernels", ("b_bytes_bf16_ratio_gm",), True),
    # sparse-C output tier (ISSUE 6): dense-strip over CompactedC C bytes
    # written, geomean over the sparse-routed (output-density ≤ threshold)
    # families — the ≥2× acceptance gate lives in bench_kernels; here the
    # diff gate keeps later PRs from eroding it
    ("kernels", ("c_bytes_ratio_gm",), True),
]

# absolute ceilings checked on the *newest* artifact alone (no baseline
# pair needed): (table, key-path, max_allowed). The obs tier's tracing
# overhead is a contract, not a trend — a 2.9% -> 2.95% drift would pass
# a relative gate while eating the whole budget.
_ABS_GATED = [
    ("obs", ("tracing_overhead_frac",), 0.03),
    # resilience tier (ISSUE 8): the validation/finiteness/breaker guards
    # on the steady serving path carry a hard ≤2% budget
    ("resilience", ("guard_overhead_frac",), 0.02),
    # serving tier (ISSUE 9): the async front-end's queue/estimator/
    # coalescing mechanics carry the same hard ≤2% budget on steady
    # cache-hit traffic
    ("serving", ("frontend_overhead_frac",), 0.02),
]

# absolute floors, the dual of the ceilings above: (table, key-path,
# min_allowed), checked on the newest artifact alone. The batching
# tier's amortization is a contract — a committed artifact where the
# batched burst stopped amortizing launches must fail the gate even
# with no baseline pair to diff against.
_ABS_FLOOR_GATED = [
    # serving tier (ISSUE 10): the 8-member batched burst must keep
    # serving >= 2 requests per kernel launch at >= 95% goodput
    ("serving", ("batch_launch_amortization",), 2.0),
    ("serving", ("batched_goodput",), 0.95),
]


def git_sha() -> str:
    """Short HEAD sha, suffixed ``-dirty`` when the tree has uncommitted
    changes — an artifact generated mid-PR must not be attributed to the
    previous PR's commit."""
    cwd = os.path.dirname(__file__)
    try:
        sha = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=cwd,
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "nogit"
    try:
        dirty = subprocess.run(
            ["git", "diff-index", "--quiet", "HEAD", "--"], cwd=cwd,
            stderr=subprocess.DEVNULL).returncode != 0
    except Exception:
        dirty = False
    return f"{sha}-dirty" if dirty else sha


def _geomean(xs) -> float:
    from benchmarks.common import geomean
    return geomean([x for x in xs if x])


# ---------------------------------------------------------------------------
# per-table summarizers: raw run() return → schema'd metrics
# ---------------------------------------------------------------------------


def _sum_fig2(res: dict) -> dict:
    per_algo = res.get("per_algo", {})
    return {"geomean_speedup_by_reorder": {
        algo: _geomean(list(sp.values())) for algo, sp in per_algo.items()}}


def _sum_fig3(res: dict) -> dict:
    per_scheme = res.get("per_scheme", {})
    return {"geomean_speedup_by_scheme": {
        s: _geomean(list(sp.values())) for s, sp in per_scheme.items()}}


def _sum_fig10(res: dict) -> dict:
    ratios = np.asarray(res.get("preprocess_ratios", []), dtype=np.float64)
    out = {}
    if ratios.size:
        out["preprocess_ratio_median"] = float(np.median(ratios))
        out["frac_under_20x"] = float((ratios <= 20.0).mean())
    methods = res.get("methods", {})
    out["amortize_within_20_by_method"] = {
        m: float((np.asarray(v) <= 20.0).mean())
        for m, v in methods.items() if len(v)}
    return out


def _sum_ratio_map(key_in: str, key_out: str):
    def f(res: dict) -> dict:
        return {key_out: {k: _geomean(v)
                          for k, v in res.get(key_in, {}).items()}}
    return f


def _sum_fig11(res: dict) -> dict:
    return {"memory_ratio_median_by_scheme": {
        k: float(np.median(np.asarray(v)))
        for k, v in res.get("ratios", {}).items() if len(v)}}


def _sum_planner(res: dict) -> dict:
    return dict(res.get("summary", {}))


def _sum_tallskinny(res: dict) -> dict:
    per_algo = res.get("per_algo", {})
    return {"geomean_speedup_by_reorder": {
        algo: _geomean(list(sp.values())) for algo, sp in per_algo.items()}}


def _sum_preprocess(res: dict) -> dict:
    by_stage = {k: _geomean(v) for k, v in res.get("speedups", {}).items()}
    out = {"engine_speedup_gm_by_stage": by_stage}
    vals = [v for v in by_stage.values() if v and np.isfinite(v)]
    if vals:
        out["engine_speedup_gm_overall"] = _geomean(vals)
    return out


def _sum_kernels(res: dict) -> dict:
    s = res.get("summary", {})
    keys = ("b_bytes_ratio_tiled_gm", "b_bytes_ratio_routed_gm",
            "routed_pallas_pct", "interp_parity_max_err",
            "interp_parity_bf16_rel_err", "grid_steps_per_mxu_gm",
            "a_bytes_ratio_compact_gm", "b_bytes_bf16_ratio_gm",
            "shard_balance_worst",
            "interp_parity_sharded_max_err", "pallas_wallclock_speedup_gm",
            "c_bytes_ratio_gm", "c_window_density_gm",
            "interp_parity_sparse_c_max_err")
    return {k: float(s[k]) for k in keys if k in s}


def _sum_obs(res: dict) -> dict:
    s = res.get("summary", {})
    keys = ("tracing_overhead_frac", "t_off_s", "t_on_s",
            "requests_per_pass", "spans_per_request")
    return {k: float(s[k]) for k in keys if k in s}


def _sum_resilience(res: dict) -> dict:
    s = res.get("summary", {})
    keys = ("guard_overhead_frac", "t_off_s", "t_on_s",
            "requests_per_pass", "chaos_requests", "faults_fired",
            "ladder_fallbacks")
    return {k: float(s[k]) for k in keys if k in s}


def _sum_serving(res: dict) -> dict:
    s = res.get("summary", {})
    keys = ("frontend_overhead_frac", "t_direct_s", "t_frontend_s",
            "requests_per_pass", "burst_submitted", "burst_admitted",
            "burst_shed", "burst_coalesced", "burst_goodput",
            "deadline_missed_completions", "batched_burst_members",
            "batch_launches", "batch_launch_amortization",
            "batched_goodput")
    return {k: float(s[k]) for k in keys if k in s}


_SUMMARIZERS = {
    "fig2": _sum_fig2,
    "fig3": _sum_fig3,
    "fig10": _sum_fig10,
    "fig11": _sum_fig11,
    "traffic": _sum_ratio_map("ratios", "fetch_ratio_gm_by_scheme"),
    "planner": _sum_planner,
    "table3": _sum_tallskinny,
    "preprocess": _sum_preprocess,
    "kernels": _sum_kernels,
    "obs": _sum_obs,
    "resilience": _sum_resilience,
    "serving": _sum_serving,
}


def build_artifact(tier: str, results: dict[str, dict]) -> dict:
    from repro import benchlib
    tables = {}
    for key, res in results.items():
        if not isinstance(res, dict):
            continue
        fn = _SUMMARIZERS.get(key)
        try:
            tables[key] = fn(res) if fn else {"raw_keys": sorted(res)}
        except Exception as e:          # a summary must never kill the sweep
            tables[key] = {"summary_error": f"{type(e).__name__}: {e}"}
    return {
        "schema": SCHEMA,
        "tier": tier,
        "git_sha": git_sha(),
        "kernel_gen": getattr(benchlib, "_KERNEL_GEN", "unknown"),
        "created_unix": int(time.time()),
        "tables": tables,
    }


def artifact_path(tier: str, sha: str) -> str:
    return os.path.join(EXPERIMENTS_DIR, f"BENCH_{tier}_{sha}.json")


def write_artifact(artifact: dict) -> str:
    os.makedirs(EXPERIMENTS_DIR, exist_ok=True)
    path = artifact_path(artifact["tier"], artifact["git_sha"])
    with open(path, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def list_artifacts(tier: str) -> list[str]:
    """Committed-state artifacts of a tier, oldest first. ``-dirty``
    snapshots (mid-PR runs, gitignored) never serve as baselines."""
    paths = [p for p in glob.glob(
        os.path.join(EXPERIMENTS_DIR, f"BENCH_{tier}_*.json"))
        if not p.endswith("-dirty.json")]
    return sorted(paths, key=lambda p: json.load(open(p)).get(
        "created_unix", 0))


# ---------------------------------------------------------------------------
# the diff gate
# ---------------------------------------------------------------------------


def _metric_values(artifact: dict, table: str, path: tuple) -> dict:
    """Flatten a gated metric into {leaf_name: value} (scalars and maps)."""
    node = artifact.get("tables", {}).get(table, {})
    for k in path:
        node = node.get(k, {}) if isinstance(node, dict) else {}
    if isinstance(node, dict):
        return {k: v for k, v in node.items()
                if isinstance(v, (int, float)) and np.isfinite(v)}
    if isinstance(node, (int, float)) and np.isfinite(node):
        return {path[-1]: float(node)}
    return {}


def compare(old: dict, new: dict,
            threshold: float = REGRESSION_THRESHOLD) -> list[str]:
    """Regressions of ``new`` vs ``old``: >threshold drop on a gated
    geomean (or rise, for lower-is-better metrics like planner regret)."""
    regressions = []
    for table, path, higher_better in _GATED:
        ov = _metric_values(old, table, path)
        nv = _metric_values(new, table, path)
        for k in sorted(set(ov) & set(nv)):
            o, n = ov[k], nv[k]
            if o <= 0:
                continue
            change = (n - o) / o
            bad = change < -threshold if higher_better \
                else change > threshold
            if bad:
                regressions.append(
                    f"{table}.{'.'.join(path)}.{k}: {o:.4g} -> {n:.4g} "
                    f"({change:+.1%})")
    return regressions


def check_absolute(artifact: dict) -> list[str]:
    """Violations of the ``_ABS_GATED`` ceilings or ``_ABS_FLOOR_GATED``
    floors in one artifact. A floor metric absent from the artifact is
    not a violation — older artifacts predate the batching tier."""
    bad = []
    for table, path, ceiling in _ABS_GATED:
        for k, v in _metric_values(artifact, table, path).items():
            if v > ceiling:
                bad.append(f"{table}.{'.'.join(path)}.{k}: {v:.4g} "
                           f"exceeds ceiling {ceiling:g}")
    for table, path, floor in _ABS_FLOOR_GATED:
        for k, v in _metric_values(artifact, table, path).items():
            if v < floor:
                bad.append(f"{table}.{'.'.join(path)}.{k}: {v:.4g} "
                           f"below floor {floor:g}")
    return bad


def diff_latest(tier: str, threshold: float = REGRESSION_THRESHOLD) -> int:
    paths = list_artifacts(tier)
    if paths:
        with open(paths[-1]) as f:
            newest = json.load(f)
        abs_bad = check_absolute(newest)
        if abs_bad:
            print(f"# trajectory: absolute-ceiling violation(s) in "
                  f"{os.path.basename(paths[-1])}:")
            for b in abs_bad:
                print(f"#   CEILING {b}")
            return 1
    if len(paths) < 2:
        have = ", ".join(os.path.basename(p) for p in paths) or "none"
        print(f"# trajectory: need >= 2 committed artifacts for tier "
              f"'{tier}' to diff — found {len(paths)} ({have}).")
        print("# baseline re-anchored: stale pre-seed artifacts were "
              "retired; `benchmarks/run.py --tier quick` at a clean "
              "commit emits the fresh baseline. The gate passes until "
              "an artifact pair exists.")
        return 0
    old_p, new_p = paths[-2], paths[-1]
    with open(old_p) as f:
        old = json.load(f)
    with open(new_p) as f:
        new = json.load(f)
    print(f"# trajectory diff: {os.path.basename(old_p)} -> "
          f"{os.path.basename(new_p)}")
    regs = compare(old, new, threshold)
    if regs:
        print(f"# {len(regs)} regression(s) beyond {threshold:.0%}:")
        for r in regs:
            print(f"#   REGRESSION {r}")
        return 1
    print("# no geomean regressions beyond threshold")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", default="quick")
    ap.add_argument("--diff", action="store_true",
                    help="compare the two newest artifacts of the tier")
    ap.add_argument("--threshold", type=float,
                    default=REGRESSION_THRESHOLD)
    args = ap.parse_args()
    if args.diff:
        sys.exit(diff_latest(args.tier, args.threshold))
    for p in list_artifacts(args.tier):
        print(p)


if __name__ == "__main__":
    main()
