#!/usr/bin/env python
"""Trace report CLI (`make trace-report`): summarize a serving trace.

Reads a span JSONL file (``repro.obs.trace.Tracer.export_jsonl``) and
prints:

* **top spans by self-time** — per span name: count, total, self
  (total minus the time spent in child spans — where the wall time
  actually went, not double-counted through the nesting);
* **plan-cache hit rate** — from the ``plan`` spans' ``cache_hit``
  attribute (and the exec-cache packing count from ``pack`` spans);
* **cost-model drift table** — per scheme, from the ``execute`` spans'
  ``residual`` attributes (the drift auditor's log-space residuals);
* **per-tenant breakdown** — request count and wall time per ``tenant``
  from the root ``request`` spans.

``--generate`` first runs a small in-process serving workload (two
tenants, repeated patterns for cache hits, one chain request) with the
tracer + device-counter emission enabled and exports
``experiments/traces/trace.jsonl`` + ``trace_chrome.json`` (load the
latter in https://ui.perfetto.dev). ``--check`` then asserts the trace
is structurally sound — every request span owns nested plan + execute
spans carrying fingerprint/scheme attributes — which is what the
``make test`` smoke invocation relies on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "src"))

TRACE_DIR = os.path.join(ROOT, "experiments", "traces")
TRACE_JSONL = os.path.join(TRACE_DIR, "trace.jsonl")
TRACE_CHROME = os.path.join(TRACE_DIR, "trace_chrome.json")


def load_spans(path: str) -> list[dict]:
    """Parse one span dict per JSONL line (blank lines skipped)."""
    spans = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                spans.append(json.loads(line))
    return spans


def summarize(spans: list[dict]) -> dict:
    """Aggregate a span list into the report's four tables."""
    by_id = {s["span_id"]: s for s in spans}
    child_time: dict[int, float] = defaultdict(float)
    for s in spans:
        if s["parent_id"] in by_id:
            child_time[s["parent_id"]] += s["dur"]

    names: dict[str, dict] = {}
    for s in spans:
        row = names.setdefault(s["name"],
                               {"count": 0, "total_s": 0.0, "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += s["dur"]
        row["self_s"] += max(s["dur"] - child_time.get(s["span_id"], 0.0),
                             0.0)

    plans = [s for s in spans if s["name"] == "plan"]
    hits = sum(1 for s in plans if s["attrs"].get("cache_hit"))
    cache = {
        "plan_calls": len(plans),
        "plan_cache_hits": hits,
        "plan_cache_hit_rate": hits / len(plans) if plans else 0.0,
        "exec_cache_packs": sum(1 for s in spans if s["name"] == "pack"
                                and s["attrs"].get("kind") != "refill"),
        "exec_cache_refills": sum(1 for s in spans if s["name"] == "pack"
                                  and s["attrs"].get("kind") == "refill"),
    }

    drift: dict[str, dict] = {}
    for s in spans:
        if s["name"] != "execute" or "residual" not in s["attrs"]:
            continue
        row = drift.setdefault(s["attrs"].get("scheme", "?"),
                               {"n": 0, "_sum_abs": 0.0, "_sum_pos": 0.0})
        r = float(s["attrs"]["residual"])
        row["n"] += 1
        row["_sum_abs"] += abs(r)
        row["_sum_pos"] += max(r, 0.0)
    for row in drift.values():
        row["mean_abs_residual"] = row.pop("_sum_abs") / row["n"]
        row["regret"] = row.pop("_sum_pos") / row["n"]

    tenants: dict[str, dict] = {}
    for s in spans:
        if s["name"] != "request":
            continue
        row = tenants.setdefault(s["attrs"].get("tenant", ""),
                                 {"requests": 0, "total_s": 0.0,
                                  "cache_hits": 0})
        row["requests"] += 1
        row["total_s"] += s["dur"]
        row["cache_hits"] += bool(s["attrs"].get("cache_hit"))

    return {"spans": names, "cache": cache, "drift": drift,
            "tenants": tenants}


def check_structure(spans: list[dict]) -> list[str]:
    """Structural assertions for `--check`: every request span owns
    nested plan and execute spans, each carrying fingerprint + scheme."""
    errors = []
    if not spans:
        return ["no spans in trace"]
    children = defaultdict(list)
    for s in spans:
        children[s["trace_id"]].append(s)
    requests = [s for s in spans if s["name"] == "request"]
    if not requests:
        errors.append("no request spans in trace")
    for req in requests:
        fam = {s["name"]: s for s in children[req["trace_id"]]}
        # chain requests routed through the sparse-C tier run a kernel
        # span per hop instead of a dense execute span
        needed = (("plan", "kernel")
                  if req["attrs"].get("workload") == "chain"
                  and "execute" not in fam else ("plan", "execute"))
        for need in needed:
            sub = fam.get(need)
            if sub is None:
                errors.append(f"request {req['trace_id']}: no nested "
                              f"'{need}' span")
                continue
            for attr in ("fingerprint", "scheme"):
                if attr not in sub["attrs"] and need != "kernel":
                    errors.append(f"request {req['trace_id']}: '{need}' "
                                  f"span missing attr '{attr}'")
    for s in spans:
        if s["parent_id"] and not any(p["span_id"] == s["parent_id"]
                                      for p in children[s["trace_id"]]):
            errors.append(f"span {s['span_id']} ({s['name']}): parent "
                          f"{s['parent_id']} not in its trace")
    return errors


def generate(tier: str = "quick") -> str:
    """Run a small in-process serving workload under tracing and export
    the span buffer; returns the JSONL path."""
    import numpy as np

    from repro.core.formats import HostCSR
    from repro.obs.metrics import get_registry
    from repro.obs.trace import get_tracer
    from repro.serve.engine import SpGEMMServer

    tracer = get_tracer().enable()
    tracer.clear()
    get_registry().device_emission = True
    rng = np.random.default_rng(7)
    n = 96 if tier == "quick" else 256

    def mat(seed_shift: int, density: float) -> HostCSR:
        r = np.random.default_rng(7 + seed_shift)
        return HostCSR.from_dense(
            (r.random((n, n)) < density).astype(np.float32))

    servers = {t: SpGEMMServer(tenant=t) for t in ("team-a", "team-b")}
    for tenant, srv in servers.items():
        for pattern in range(2):
            a = mat(pattern, 0.06)
            for _ in range(3):                  # repeats → plan-cache hits
                srv.submit(a)
        srv.submit(mat(5, 0.05),
                   rng.standard_normal((n, 32)).astype(np.float32))
    servers["team-a"].submit(mat(9, 0.04), hops=2)      # one chain request

    os.makedirs(TRACE_DIR, exist_ok=True)
    nspans = tracer.export_jsonl(TRACE_JSONL)
    tracer.export_chrome(TRACE_CHROME)
    print(f"trace-report: generated {nspans} spans -> {TRACE_JSONL}")
    print(f"trace-report: chrome trace -> {TRACE_CHROME} "
          "(load in https://ui.perfetto.dev)")
    return TRACE_JSONL


def _table(title: str, header: list[str], rows: list[list]) -> None:
    print(f"\n{title}")
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows), 1)
              if rows else len(str(h)) for i, h in enumerate(header)]
    line = "  ".join(str(h).ljust(w) for h, w in zip(header, widths))
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))


def render(summary: dict) -> None:
    rows = sorted(summary["spans"].items(),
                  key=lambda kv: kv[1]["self_s"], reverse=True)
    _table("top spans by self-time", ["span", "count", "total_s", "self_s"],
           [[name, v["count"], f"{v['total_s']:.4f}", f"{v['self_s']:.4f}"]
            for name, v in rows])
    c = summary["cache"]
    _table("caches", ["plan_calls", "hits", "hit_rate", "exec_packs",
                      "exec_refills"],
           [[c["plan_calls"], c["plan_cache_hits"],
             f"{c['plan_cache_hit_rate']:.2f}", c["exec_cache_packs"],
             c["exec_cache_refills"]]])
    _table("cost-model drift (log-space residual, per scheme)",
           ["scheme", "n", "mean_abs_residual", "regret"],
           [[s, v["n"], f"{v['mean_abs_residual']:.4f}",
             f"{v['regret']:.4f}"]
            for s, v in sorted(summary["drift"].items())])
    _table("per-tenant", ["tenant", "requests", "total_s", "cache_hits"],
           [[t or "(default)", v["requests"], f"{v['total_s']:.4f}",
             v["cache_hits"]]
            for t, v in sorted(summary["tenants"].items())])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", default=TRACE_JSONL,
                    help="span JSONL to report on (default: "
                         "experiments/traces/trace.jsonl)")
    ap.add_argument("--generate", action="store_true",
                    help="run the in-process serving workload under "
                         "tracing first and export the trace")
    ap.add_argument("--tier", default="quick", choices=["quick", "full"],
                    help="workload size for --generate")
    ap.add_argument("--check", action="store_true",
                    help="assert the trace's span structure (nested "
                         "plan/execute with fingerprint+scheme attrs)")
    args = ap.parse_args(argv)

    path = generate(args.tier) if args.generate else args.trace
    if not os.path.exists(path):
        print(f"trace-report: no trace at {path} (run with --generate)")
        return 1
    spans = load_spans(path)
    render(summarize(spans))
    if args.check:
        errors = check_structure(spans)
        if errors:
            for e in errors:
                print(f"trace-report: CHECK FAILED: {e}")
            return 1
        print(f"\ntrace-report: structure check passed "
              f"({len(spans)} spans)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
