"""Each cell's whole run at a size the CPU holds (Pallas in interpret
mode), with the chip check skipped: correct when the program is sound,
not correct when the answer is altered where the kernel produces it,
and not correct for the control (the reference at the precision below
the configuration's) or the program's own bf16-B path."""
import json

import numpy as np
import pytest

from chipbench import control, run
from chipbench.tests.conftest import CELLS


def _alter(out):
    """One entry of the kernel's answer off by one part in a thousand."""
    import jax.numpy as jnp
    return out.at[0, 0].multiply(jnp.float32(1.001))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, tiny):
    out = run.run_cell(tiny(name), 2**31 + 7, 1.0, False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(tiny(name).end_to_end)
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_the_program_spans(name, tiny):
    cell = tiny(name)
    out = run.run_cell(cell, 11, 1.0, True)
    assert out["correct"], out["checks"]
    spans = {"plan_ms", "pack_s", "return_s", "queue_wait_p95_s"}
    assert spans & set(cell.per_layer) <= set(out["metrics"])
    assert "plan_ms" in out["metrics"] and "return_s" in out["metrics"]
    # no device plane on the CPU: no device metric is made up
    assert not any(m.startswith(("kernel_device", "device_idle"))
                   or m.endswith("_roofline") for m in out["metrics"])
    json.dumps(out)


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_in_the_kernel_is_not_correct(name, tiny,
                                                     monkeypatch):
    from repro.kernels import ops
    for fn in ("bcc_spgemm_tiled", "bcc_spmm_compact"):
        orig = getattr(ops, fn)
        monkeypatch.setattr(ops, fn, lambda *a, _f=orig, **k: _alter(
            _f(*a, **k)))
    out = run.run_cell(tiny(name), 5, 1.0, False)
    assert not out["correct"]
    assert out["checks"]["max_err"]["value"] > \
        out["checks"]["max_err"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_its_limit(name, tiny):
    cell = tiny(name)
    got = control.control_reading(cell, 9, 2)
    assert got["max_err"] > cell.config["limits"]["max_err"], got
    assert not control.control_correct(cell, got)


def test_bf16_b_path_of_the_program_is_not_correct(tiny):
    import jax.numpy as jnp
    out = run.run_cell(tiny("g500s12-a2-revalue"), 3, 1.0, False,
                       pallas_b_dtype=jnp.bfloat16)
    assert not out["correct"]


def test_nan_answer_reads_as_infinite_error():
    from chipbench.products import a2
    import scipy.sparse as sp
    ref = sp.csr_matrix(np.array([[1.0, 0.0], [0.0, 2.0]]))
    got = np.array([[1.0, 0.0], [0.0, np.nan]], np.float32)
    assert a2._compare_dense(got, ref) == (float("inf"), float("inf"))
    assert a2._compare_dense(np.eye(2, dtype=np.float32) * [1, 2],
                             ref) == (0.0, 0.0)


def test_isolated_vertex_with_a_zero_feature_reads_exactly():
    """A vertex with only its self loop and a feature of exactly 0 has a
    rounding scale of 0: an exact answer there reads 0, not 0/0."""
    from chipbench.products import gcn
    cfg = {"scale": 7, "edgefactor": 16, "graph_seed": 0, "features": 4,
           "initiator": {"a": 0.57, "b": 0.19, "c": 0.19}}
    dep = gcn.Deployment(cfg, 1, {})
    lone = np.flatnonzero(np.diff(dep.indptr) == 1)
    assert lone.size
    payload = dep.payload(0)
    x = payload[1]
    x[lone[0], :] = 0.0
    y = (dep._a64 @ x.astype(np.float64)).astype(np.float32)
    got = dep.check([(payload, y)])["max_err"]
    assert np.isfinite(got) and got < 1e-6
    y[lone[0], 0] = 1e-30
    assert dep.check([(payload, y)])["max_err"] == float("inf")


def test_stale_packed_operands_are_not_correct(tiny, monkeypatch):
    """The exec cache keyed without the values: every request is served
    from the operands packed for the first value set it saw."""
    from repro.planner import service
    monkeypatch.setattr(service, "_value_digest", lambda m: "stale")
    out = run.run_cell(tiny("g500s12-a2-revalue"), 13, 1.0, False)
    assert not out["correct"]
    assert out["checks"]["max_err"]["value"] > \
        out["checks"]["max_err"]["limit"]
