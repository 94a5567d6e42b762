"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--tier quick|default|full]
                                            [--only fig2,fig3,...]
                                            [--no-artifact]

Tiers: quick (8 matrices, 5 reorderings — CI-speed), default (24 matrices,
all 10 reorderings), full (the whole 110-matrix suite; hours on CPU).
Measurements are cached in experiments/bench_cache.json so Table 2 / Fig. 10
reuse the Fig. 2/3 sweep, like the paper does. Full runs (no ``--only``)
additionally emit a schema'd perf-trajectory artifact
``experiments/BENCH_<tier>_<sha>.json`` (see benchmarks/trajectory.py) —
tracked in git, diffed across PRs.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro import benchlib
from repro.compile_cache import enable_compile_cache

from benchmarks import (bench_clusterwise, bench_kernels, bench_memory,
                        bench_obs, bench_overhead, bench_planner,
                        bench_preprocess, bench_reorder_rowwise,
                        bench_resilience, bench_serving, bench_tallskinny,
                        bench_traffic, roofline_report, trajectory)

TABLES = {
    "fig2": ("Fig.2/Table2 row-wise reorder", bench_reorder_rowwise.run),
    "fig3": ("Fig.3/Fig.8/Table2 cluster-wise", bench_clusterwise.run),
    "table3": ("Table3/Table4 tall-skinny", bench_tallskinny.run),
    "fig10": ("Fig.10 amortization", bench_overhead.run),
    "fig11": ("Fig.11 memory", bench_memory.run),
    "traffic": ("B-fetch traffic model (mechanism)", bench_traffic.run),
    "kernels": ("Pallas Sp×Sp vs XLA + BCC occupancy/VMEM",
                bench_kernels.run),
    "preprocess": ("Segmented-CSR preprocessing engine vs loop references",
                   bench_preprocess.run),
    "planner": ("ISSUE-2 planner vs best/worst-static", bench_planner.run),
    "obs": ("Tracing/metrics overhead + stage breakdown", bench_obs.run),
    "resilience": ("Resilience guard overhead + chaos recovery",
                   bench_resilience.run),
    "serving": ("Async front-end overhead + overload goodput",
                bench_serving.run),
    "roofline": ("TPU roofline (from dry-run)", roofline_report.run),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", choices=["quick", "default", "full"],
                    default="quick")
    ap.add_argument("--only", help="comma-separated table keys")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip writing the BENCH_<tier>_<sha>.json artifact")
    args = ap.parse_args()
    enable_compile_cache()

    keys = list(TABLES) if not args.only else args.only.split(",")
    benchlib.load_cache()
    t_all = time.time()
    results: dict[str, dict] = {}
    failures: list[str] = []
    for k in keys:
        title, fn = TABLES[k]
        print(f"\n===== {k}: {title} (tier={args.tier}) =====")
        t0 = time.time()
        try:
            results[k] = fn(args.tier)
        except Exception as e:    # keep the harness going; report at end
            print(f"# {k} FAILED: {type(e).__name__}: {e}")
            failures.append(k)
        finally:
            benchlib.save_cache()
        print(f"# {k} done in {time.time()-t0:.1f}s")
    print(f"\n# all benchmarks done in {time.time()-t_all:.1f}s")
    if failures:
        # completed tables' measurements are cached, but an artifact must
        # cover every table — the trajectory diff silently skips absent
        # metrics, so a partial artifact would defeat the regression gate
        print(f"# FAILED tables: {','.join(failures)} — no trajectory "
              "artifact written")
        sys.exit(1)
    if args.no_artifact:
        return
    if args.only:
        # a partial run must not overwrite the tier's full artifact
        print("# trajectory artifact skipped (--only run; drop --only to "
              "emit one)")
        return
    path = trajectory.write_artifact(
        trajectory.build_artifact(args.tier, results))
    print(f"# trajectory artifact: {path}")


if __name__ == "__main__":
    main()
