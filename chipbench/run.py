"""Run one benchmark cell on the chip and print its result line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the cell's operands from the seed, starts an
``AsyncSpGEMMServer`` with the configuration's plan in its plan cache and
sends the warm-up requests. The window then drives the server with the
cell's traffic for ``--seconds``. Afterwards the server is closed, and a
sample of the responses, drawn from the seed, is compared with the plain
reference. The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read from the program's obs
spans and a profiler trace of the window), ``device``, ``breakdown``
(``--trace 1``) and last ``checks``, each number compared with its
limit, which also end stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import generator, manifest, readings, trace  # noqa: E402

# where the numbers compared come from, besides the deployment's own
ERRORED = "errored"       # requests that raised (not shed): limit 0
DEGRADED = "degraded"     # responses served off the configured plan


def make_server(dep, workload: str, cfg: dict, **planner_kw):
    """The serving path users call: an ``AsyncSpGEMMServer`` with one
    worker thread over a planner whose plan cache holds the
    configuration's plan for the deployment's operand."""
    from repro.planner import Candidate, Planner
    from repro.planner.features import fingerprint
    from repro.planner.plan_cache import Plan, PlanCache
    from repro.resilience.policy import ResiliencePolicy
    from repro.serve.engine import SpGEMMServer
    from repro.serve.frontend import AsyncSpGEMMServer
    reorder, scheme = cfg["plan"]["reorder"], cfg["plan"]["scheme"]
    planner = Planner(cache=PlanCache(), resilience=ResiliencePolicy(),
                      candidates=(Candidate(reorder, scheme),), **planner_kw)
    planner.cache.put(Plan(
        fingerprint=fingerprint(dep.operand), reorder=reorder,
        scheme=scheme, reuse_hint=cfg["reuse_hint"],
        workload=workload))
    return AsyncSpGEMMServer(SpGEMMServer(planner=planner), workers=1)


def enable_compile_cache() -> str:
    import jax
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    # every program, however quick to compile, so that set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _device(count: int) -> dict:
    import jax
    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    return {"platform": d.platform, "kind": d.device_kind, "count": count,
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def run_cell(cell: manifest.Cell, seed: int, seconds: float, traced: bool,
             t_start: float = T_START, **planner_kw) -> dict:
    """One run of ``cell``: set-up, the window, the check. Returns the
    result object (``checks`` last); ``planner_kw`` reach the planner."""
    import jax
    enable_compile_cache()
    parts = {"start": time.perf_counter() - t_start}
    cfg, traffic = cell.config, cell.traffic
    dep = cell.product.Deployment(cfg, seed, traffic)
    server = make_server(dep, cell.product.WORKLOAD, cfg, **planner_kw)
    hint = int(cfg["reuse_hint"])
    parts["operands"] = time.perf_counter() - t_start - parts["start"]
    generator.warm_up(server, dep, traffic, hint)
    setup_s = time.perf_counter() - t_start
    parts["warm_up"] = setup_s - parts["operands"] - parts["start"]

    keep = generator.Reservoir(int(traffic.get("sample", 4)),
                               generator.rng(seed, 3))
    spans = dev_trace = None
    log_dir = None
    if traced:
        from repro.obs.trace import get_tracer
        tracer = get_tracer()
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        tracer.clear()
        tracer.enable()
        with jax.profiler.TraceAnnotation(trace.ANCHOR), \
                tracer.span(readings.SPAN_ANCHOR):
            anchor = time.perf_counter()
    try:
        records = generator.run(server, dep, traffic, seconds, hint, keep)
    finally:
        if traced:
            tracer.disable()
            jax.profiler.stop_trace()
    device = _device(len(jax.devices()))
    served = [r for r in records if r.done is not None]
    if traced:
        spans = readings.spans_on_perf_clock(tracer.spans(), anchor)
        tracer.clear()
        dev_trace = trace.reduce_planes(
            trace.read_planes(trace.find_xplane(log_dir)), anchor)
        shutil.rmtree(log_dir, ignore_errors=True)
        if records:
            dev_trace.window = (dev_trace.to_trace(records[0].due),
                                dev_trace.to_trace(max(
                                    (r.done for r in served),
                                    default=records[-1].sent)))
        device["busy_s"] = dev_trace.busy_s()
        device["window_s"] = dev_trace.window_s
    server.close()
    del server
    gc.collect()

    checks = dep.check(keep.items) if keep.items else {}
    checks[ERRORED] = sum(r.error.startswith("error") for r in records)
    checks[DEGRADED] = sum(r.degraded for r in served)
    limits = dict(cfg["limits"], **{ERRORED: 0, DEGRADED: 0})
    correct = bool(keep.items) and judge(checks, limits)

    ctx = readings.Context(records=records, setup_s=setup_s, spans=spans,
                           trace=dev_trace, base=cell.base)
    names = cell.per_layer if traced else cell.end_to_end
    if traced:
        from chipbench.work import peaks
        ctx.work = dep.work()
        ctx.peak = peaks(device["kind"]) if device["platform"] != "cpu" \
            else None
    metrics = {}
    for name in names:
        v = ctx.value(name)
        if v is not None and np.isfinite(v):
            metrics[name] = {"value": v,
                             "unit": manifest.metric(name, cell.base).UNIT}
    out = {"correct": correct, "attempted": len(records),
           "failed": len(records) - len(served), "metrics": metrics,
           "device": device}
    if traced:
        bd = readings.breakdown(ctx)
        if bd is not None:
            out["breakdown"] = bd
    out["notes"] = {
        "downgraded": sum(r.downgraded for r in served),
        "shed": sum(r.error.startswith("shed") for r in records),
        "not_pallas": sum(r.kernel_path != "pallas" for r in served),
        "sampled": len(keep.items),
        "setup_parts_s": parts}
    out["checks"] = {k: {"value": _finite(checks[k]), "limit": limits[k]}
                     for k in checks}
    return out


def judge(checks: dict, limits: dict) -> bool:
    """Whether every number compared is within its limit; a number that
    is not finite fails."""
    return all(np.isfinite(checks[k]) and checks[k] <= limits[k]
               for k in checks)


def _finite(v):
    """JSON has no infinity: a reading that is not finite prints as the
    largest float, which fails every limit."""
    return v if np.isfinite(v) else sys.float_info.max


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = manifest.load_cell(args.workload)
    import jax
    devs = jax.devices()
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if devs[0].platform != "tpu":
        print("chipbench: no TPU found; nothing was run", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print_result(out)
    return 0


def print_result(out: dict) -> None:
    print("notes: " + " ".join(f"{k}={v}" for k, v in out["notes"].items()),
          file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
