"""The reduction from a profiler trace to device time, on a small trace
written out by hand in the layout ``trace.read_planes`` returns."""
import json
import os

import numpy as np
import pytest

from chipbench import readings, trace
from chipbench.generator import Record
from chipbench.readings import Context


KERNEL = ('%closed_call.13 = f32[16,16]{1,0} custom-call(s32[2]{0} %p), '
          'custom_call_target="tpu_custom_call"')


def _planes():
    """Device 0 runs three kernels and a copy; the host annotates the
    anchor 5 µs after the trace's start (times in ns)."""
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [(trace.ANCHOR, 5_000, 1_000)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_cluster_spgemm_pairs_db(123)", 10_000, 25_000),
                ("jit_copy(4)", 50_000, 5_000),
                ("jit_cluster_spmm_compact(567)", 70_000, 10_000)]},
            {"name": "XLA Ops", "events": [
                (KERNEL, 10_000, 20_000),
                ("%fusion.2 = s32[2]{0} fusion(s32[1]{0} %b), kind=kLoop",
                 25_000, 10_000),                              # overlaps
                ("%copy.1 = f32[8]{0} copy(f32[8]{0} %a)", 50_000, 5_000),
                (KERNEL, 70_000, 10_000)]}]},
    ]


@pytest.fixture
def dev():
    t = trace.reduce_planes(_planes(), anchor_perf_s=100.0)
    t.window = (t.to_trace(100.0 - 5e-6), t.to_trace(100.0 + 95e-6))
    return t


def test_anchor_maps_perf_counter_onto_the_trace_clock(dev):
    assert dev.to_trace(100.0) == pytest.approx(5e-6)
    assert dev.window == pytest.approx((0.0, 100e-6))


def test_busy_is_the_union_of_device_operations(dev):
    # [10, 35] ∪ [50, 55] ∪ [70, 80] µs
    assert dev.busy_s() == pytest.approx(40e-6)
    assert dev.window_s == pytest.approx(100e-6)


def test_kernel_time_by_the_name_of_its_program(dev):
    assert dev.module_seconds(r"^jit_cluster_spgemm") == pytest.approx(25e-6)
    assert dev.module_seconds(r"^jit_cluster_spmm") == pytest.approx(10e-6)
    assert dev.module_seconds(r"^jit_nothing") == 0.0


def test_operations_are_named_by_program_and_instruction(dev):
    ops = dev.op_seconds()
    assert ops == {
        "jit_cluster_spgemm_pairs_db/closed_call.13 custom-call":
            pytest.approx(20e-6),
        "jit_cluster_spgemm_pairs_db/fusion.2 fusion": pytest.approx(10e-6),
        "jit_copy/copy.1 copy": pytest.approx(5e-6),
        "jit_cluster_spmm_compact/closed_call.13 custom-call":
            pytest.approx(10e-6)}


def test_window_clips_operations(dev):
    dev.window = (dev.window[0], 30e-6)
    assert dev.busy_s() == pytest.approx(20e-6)
    assert sum(dev.op_seconds().values()) == pytest.approx(25e-6)


def test_idle_gaps_are_the_window_less_busy_time(dev):
    gaps = dev.idle_gaps()
    assert gaps == [pytest.approx(g) for g in (
        (0.0, 10e-6), (35e-6, 50e-6), (55e-6, 70e-6), (80e-6, 100e-6))]
    assert sum(b - a for a, b in gaps) == pytest.approx(
        dev.window_s - dev.busy_s())


def test_idle_time_is_split_by_the_innermost_obs_span(dev):
    from repro.obs.trace import Span
    # perf_counter 100.0 is trace time 5 µs: a request span over trace
    # [0, 60] µs with a pack span over [30, 60] µs inside it
    spans = [Span("request", "t", 1, 0, 100.0 - 5e-6, 60e-6, {}),
             Span("pack", "t", 2, 1, 100.0 + 25e-6, 30e-6, {})]
    rec = Record(k=0, due=100.0, sent=100.0, done=100.0 + 50e-6)
    ctx = Context(records=[rec], setup_s=1.0, spans=spans, trace=dev)
    bd = readings.breakdown(ctx)
    idle = dict(bd["idle_gaps"])
    assert idle["request"] == pytest.approx(10e-6)
    # the gap [55, 70] µs is 5 µs of pack, then 10 µs of no request
    assert idle["pack"] == pytest.approx(20e-6)
    assert idle["no request in service"] == pytest.approx(30e-6)
    assert bd["device_ops"][0][0] == \
        "jit_cluster_spgemm_pairs_db/closed_call.13 custom-call"


def test_a_trace_without_the_anchor_is_refused():
    planes = _planes()
    planes[0]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        trace.reduce_planes(planes, anchor_perf_s=0.0)


def test_merge_unions_unsorted_overlapping_intervals():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [2.5, 4.0]])
    assert trace.merge(iv).tolist() == [[0.0, 4.0], [5.0, 6.0]]
    assert trace.union_s(iv) == pytest.approx(5.0)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_gcn_trace.json")


def _naive_busy(events, t0, t1):
    """Busy seconds by a plain sweep over sorted [start, end] pairs."""
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in events):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return busy + (cur_e - cur_s if cur_e is not None else 0.0)


def test_recorded_v5e_trace():
    """A slice of a TPU v5e trace of the GCN cell (the first device
    events of a traced run and the harness's anchor)."""
    with open(RECORDED) as f:
        rec = json.load(f)
    dev = trace.reduce_planes(rec["planes"], rec["anchor_perf_s"])
    plane = [p for p in rec["planes"] if trace.DEVICE_PLANE.match(p["name"])]
    assert len(plane) == 1 and len(dev.ops) == 1
    lines = {ln["name"]: ln["events"] for ln in plane[0]["lines"]}
    ops = [(s * 1e-9, (s + d) * 1e-9) for _, s, d in lines[trace.OP_LINE]]
    mods = lines[trace.MODULE_LINE]
    t0 = min(s for s, _ in ops)
    t1 = max(e for _, e in ops)
    dev.window = (t0, t1)
    assert dev.busy_s() == pytest.approx(_naive_busy(ops, t0, t1))
    assert 0 < dev.busy_s() <= dev.window_s
    gaps = dev.idle_gaps()
    assert sum(b - a for a, b in gaps) == pytest.approx(
        dev.window_s - dev.busy_s())
    spmm = sum(max(0.0, min((s + d) * 1e-9, t1) - max(s * 1e-9, t0))
               for n, s, d in mods if n.startswith("jit_cluster_spmm"))
    assert spmm > 0
    assert dev.module_seconds(r"^jit_cluster_spmm") == pytest.approx(spmm)
    assert all(n.startswith("jit_cluster_spmm_compact/")
               for n in dev.op_seconds())
    # the anchor annotation lies before the first device operation
    assert dev.to_trace(rec["anchor_perf_s"]) < t0
