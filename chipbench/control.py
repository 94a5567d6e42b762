"""Readings that set a cell's limits: the program's and the control's.

    python3 -m chipbench.control --workload <name> --seeds 1,2,3 \
        --seconds 8 [--control-seeds 3] [--bf16-b]

For each seed, in one process: one short run of the cell through the
timed path (``run.run_cell``), whose ``checks`` are the program's
readings. For the first ``--control-seeds`` seeds, also the control: the
plain reference computed at the precision below the configuration's
fp32-at-highest (three bf16 passes, ``lowprec.py``) and put in the
program's place, on as many payloads as a run samples, compared exactly
as a response is and judged against the cell's limits as a run is
(``run.judge``): it has to come out not correct. Beside it, the same
with the backend's own ``precision=HIGH``. ``--bf16-b`` also runs the
program with the planner's own lower-precision path (B's tiles stored
in bf16).

The benchmark's runs never run this. Without a TPU it exits 2, as the
benchmark does; the tests call ``control_reading`` on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from chipbench import manifest, run


def control_reading(cell: manifest.Cell, seed: int, requests: int,
                    matmul=None) -> dict:
    """The deployment's numbers for the control's answers to the first
    ``requests`` payloads of ``seed``; ``matmul`` replaces the
    three-pass bf16 product (``lowprec.matmul_bf16x3``)."""
    dep = cell.product.Deployment(cell.config, seed, cell.traffic)
    sample = []
    for k in range(requests):
        payload = dep.payload(k)
        sample.append((payload, cell.product.control(dep, payload, matmul)))
    return dep.check(sample)


def control_correct(cell: manifest.Cell, reading: dict) -> bool:
    """The run's verdict on the control's answers."""
    return run.judge(reading, cell.config["limits"])


def readings(cell: manifest.Cell, seed: int, seconds: float, *,
             control: bool = True, bf16_b: bool = False) -> dict:
    out = run.run_cell(cell, seed, seconds, False,
                       t_start=time.perf_counter())
    row = {"seed": seed, "correct": out["correct"],
           "sampled": out["notes"]["sampled"],
           "program": {k: c["value"] for k, c in out["checks"].items()}}
    if control:
        from chipbench.lowprec import matmul_high
        n = int(cell.traffic.get("sample", 4))
        for key, matmul in (("control", None),
                            ("control_backend_high", matmul_high)):
            got = control_reading(cell, seed, n, matmul)
            row[key] = got
            row[key + "_correct"] = control_correct(cell, got)
    if bf16_b:
        import jax.numpy as jnp
        low = run.run_cell(cell, seed, seconds, False,
                           t_start=time.perf_counter(),
                           pallas_b_dtype=jnp.bfloat16)
        row["program_bf16_b"] = {k: c["value"]
                                 for k, c in low["checks"].items()}
        row["program_bf16_b_correct"] = low["correct"]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--bf16-b", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench.control: no TPU found", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        row = readings(cell, seed, args.seconds,
                       control=i < args.control_seeds,
                       bf16_b=args.bf16_b and i < args.control_seeds)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
