"""Find a cell's configuration, traffic mix, deployment code and metric
readers by the names ``BENCHMARK.json`` gives them.

Each lives in a file of its own, so that a new one is new files plus new
entries in the manifest:

- a configuration: the ``file`` its manifest entry names (JSON), whose
  ``product`` names ``products/<product>.py``;
- a traffic mix: ``traffic/<traffic>.json``;
- a metric, end-to-end or per-layer: ``metrics/<name>.py``, with ``UNIT``
  and ``read(ctx)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    product: object            # the products/<product>.py module
    end_to_end: list           # metric names a --trace 0 run reports
    per_layer: list            # metric names a --trace 1 run reports
    base: str = HERE           # where its traffic, products, metrics are


def load_module(path: str, prefix: str):
    """Import a file under a module name made from its path, so that a
    name with dots or dashes in it is still a file of its own."""
    name = prefix + re.sub(r"\W", "_", os.path.relpath(path, HERE))
    mod = sys.modules.get(name)
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric(name: str, base: str = HERE):
    return load_module(os.path.join(base, "metrics", f"{name}.py"),
                       "chipbench_metric_")


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: str = ROOT, base: str = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``; its files are read
    from ``root``, traffic mixes, products and metrics from ``base``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(base, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    product = load_module(
        os.path.join(base, "products", f"{config['product']}.py"),
        "chipbench_product_")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        product=product,
        end_to_end=[m["name"] for m in manifest["end_to_end"]
                    if _applies(m, name)],
        per_layer=[m["name"] for m in manifest["per_layer"]
                   if _applies(m, name)],
        base=base)
