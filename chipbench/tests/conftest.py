import json
import os

import pytest

from chipbench import manifest


def _manifest() -> dict:
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in _manifest()["workloads"]]


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """Load a cell with its configuration cut to a size the CPU runs in
    interpret mode; the persistent compile cache stays off."""
    from chipbench import run
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")
    m = _manifest()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    for c in m["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg["scale"] = 7
        if "features" in cfg:
            cfg["features"] = 16
        path = tmp_path / c["file"]
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(cfg))

    def load(name):
        return manifest.load_cell(name, root=str(tmp_path))
    return load
