"""The harness finds a cell's configuration, traffic mix and metrics by
name, and a new one is new files plus new manifest entries."""
import json
import os
import shutil

import pytest

from chipbench import manifest

ROOT = manifest.ROOT


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_files_of_its_own():
    m = _manifest()
    for w in m["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert cell.product.WORKLOAD in ("a2", "spmm")
        assert "setup_s" in cell.end_to_end
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for name in cell.end_to_end + cell.per_layer:
            mod = manifest.metric(name)
            assert callable(mod.read) and mod.UNIT


def test_manifest_units_match_the_readers():
    m = _manifest()
    for entry in m["end_to_end"] + m["per_layer"]:
        assert manifest.metric(entry["name"]).UNIT == entry["unit"]


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        manifest.load_cell("no-such-cell")


def test_a_new_config_traffic_and_metric_are_files_only(tmp_path,
                                                       monkeypatch):
    """Add a configuration, a traffic mix and a per-layer metric as new
    files plus new manifest entries, with no existing file edited."""
    base = tmp_path / "chipbench"
    shutil.copytree(manifest.HERE, base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    cfg = json.loads((base / "configs" / "graph500-s12.json").read_text())
    cfg.update(name="dummy-graph", scale=6)
    (base / "configs" / "dummy-graph.json").write_text(json.dumps(cfg))
    (base / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "warmup": 1, "sample": 1}))
    (base / "metrics" / "dummy_requests.py").write_text(
        'UNIT = "1"\n\ndef read(ctx):\n    return float(len(ctx.records))\n')
    m = _manifest()
    m["configs"].append({"name": "dummy-graph", "source": "x",
                         "file": "chipbench/configs/dummy-graph.json",
                         "reduced": ["scale"], "why": "a test"})
    m["workloads"].append({"name": "dummy-cell", "config": "dummy-graph",
                           "traffic": "dummy-mix", "chips": 1, "why": "t"})
    m["per_layer"].append({"name": "dummy_requests", "unit": "1",
                           "better": "higher", "source": "host_clock",
                           "layer": "front end", "moves": "latency_p50_s",
                           "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    for p, data in before.items():
        assert p.read_bytes() == data

    cell = manifest.load_cell("dummy-cell", root=str(tmp_path),
                              base=str(base))
    assert cell.config["scale"] == 6
    assert cell.traffic == {"loop": "closed", "clients": 1,
                            "warmup": 1, "sample": 1}
    assert "dummy_requests" in cell.per_layer
    from chipbench import run
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")
    out = run.run_cell(cell, 5, 0.5, traced=True)
    assert out["correct"]
    assert out["metrics"]["dummy_requests"] == {
        "value": float(out["attempted"]), "unit": "1"}
