"""Find the highest open-loop rate a cell's server sustains.

    python3 -m chipbench.sweep --workload <name> --seed <n> \
        --rates 6,8,10,12,14 --seconds 20

One process: set-up once, then the cell's requests at each rate in turn,
as open-loop arrivals (the traffic file's own loop and rate are
ignored). For each rate it prints the p50 and p95 latency, the requests
shed and the backlog: the median latency of the last quarter of the
requests over that of the first quarter. A rate is sustained when
nothing is shed and the backlog stays under ``GROWTH``; an open-loop
cell runs at four fifths of the highest rate sustained. Responses are
not checked here: the cell's own runs do that.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from chipbench import generator, manifest, run
from chipbench.readings import nearest_rank

GROWTH = 2.0


def backlog(latencies: list[float]) -> float:
    q = max(len(latencies) // 4, 1)
    return float(np.median(latencies[-q:]) / np.median(latencies[:q]))


def sweep(cell: manifest.Cell, seed: int, rates: list[float],
          seconds: float) -> list[dict]:
    run.enable_compile_cache()
    dep = cell.product.Deployment(cell.config, seed, cell.traffic)
    server = run.make_server(dep, cell.product.WORKLOAD, cell.config)
    hint = int(cell.config["reuse_hint"])
    generator.warm_up(server, dep, cell.traffic, hint)
    rows = []
    try:
        for rate in rates:
            traffic = dict(cell.traffic, loop="open", rate_per_s=rate)
            keep = generator.Reservoir(0, generator.rng(seed, 3))
            recs = generator.run(server, dep, traffic, seconds, hint, keep)
            lat = [r.latency for r in recs]
            shed = sum(r.error.startswith("shed") for r in recs)
            late = [r.sent - r.due for r in recs if r.sent]
            row = {"rate_per_s": rate, "requests": len(recs), "shed": shed,
                   "failed": sum(r.done is None for r in recs),
                   "p50_s": nearest_rank(lat, 0.5),
                   "p95_s": nearest_rank(lat, 0.95),
                   "backlog": backlog(lat),
                   "generator_late_p95_s": nearest_rank(late, 0.95)}
            row["sustained"] = bool(shed == 0 and row["failed"] == 0
                                    and row["backlog"] < GROWTH)
            rows.append(row)
            print(json.dumps(row), flush=True)
            time.sleep(1.0)     # let the queue drain between rates
    finally:
        server.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench.sweep: no TPU found", file=sys.stderr)
        return 2
    rows = sweep(cell, args.seed, [float(r) for r in args.rates.split(",")],
                 args.seconds)
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"highest_sustained_per_s": max(ok) if ok else None,
                      "cell_rate_per_s": 0.8 * max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
