"""The per-layer metrics read from the program's transfer, front-end
and compile spans, on each cell's whole run at a size the CPU holds;
and the program's spans in the profiler's own trace."""
import time

import pytest

from chipbench import readings, run, trace
from chipbench.tests.conftest import CELLS

SPAN_METRICS = ("frontend_busy_s", "upload_s", "h2d_mib", "d2h_mib",
                "result_s", "window_compiles")


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_the_transfer_and_front_end_spans(name, tiny):
    cell = tiny(name)
    assert set(SPAN_METRICS) <= set(cell.per_layer)
    out = run.run_cell(cell, 2**31 + 19, 1.0, True)
    assert out["correct"], out["checks"]
    got = {m: out["metrics"][m]["value"] for m in SPAN_METRICS}
    assert got["window_compiles"] == 0
    for m in ("frontend_busy_s", "upload_s", "h2d_mib", "d2h_mib",
              "result_s"):
        assert got[m] > 0, (m, got)


@pytest.mark.parametrize("name", CELLS)
def test_untraced_run_reads_no_span_metric(name, tiny):
    out = run.run_cell(tiny(name), 2**31 + 23, 0.5, False)
    assert not set(SPAN_METRICS) & set(out["metrics"])


def test_obs_span_is_a_profiler_event_on_the_same_clock(tmp_path):
    """An obs span opened under the profiler is a host-plane event of
    the same name, whose start the anchor maps to within 1 ms."""
    import jax
    from repro.obs.trace import get_tracer
    tracer = get_tracer()
    was = tracer.enabled
    jax.profiler.start_trace(str(tmp_path))
    tracer.clear()
    tracer.enable()
    try:
        with jax.profiler.TraceAnnotation(trace.ANCHOR), \
                tracer.span(readings.SPAN_ANCHOR):
            anchor = time.perf_counter()
        time.sleep(0.01)
        with tracer.span("upload", bytes=0):
            time.sleep(0.005)
    finally:
        if not was:
            tracer.disable()
        jax.profiler.stop_trace()
    spans = readings.spans_on_perf_clock(tracer.spans(), anchor)
    tracer.clear()
    planes = trace.read_planes(trace.find_xplane(str(tmp_path)))
    dev = trace.reduce_planes(planes, anchor)
    events = [s * 1e-9 for p in planes
              if not trace.DEVICE_PLANE.match(p["name"])
              for ln in p["lines"] for n, s, _ in ln["events"]
              if n == "upload"]
    assert len(events) == 1
    upload = next(s for s in spans if s.name == "upload")
    assert abs(dev.to_trace(upload.t0) - events[0]) < 1e-3
