"""What a metric reader reads: the window's requests, the program's obs
spans, the device trace and the work of one request."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from chipbench import manifest

SPAN_ANCHOR = "chipbench.anchor"


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile (0 < q <= 1) by nearest rank: a value that was
    read, and infinity once more than ``1 - q`` of them are."""
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(math.ceil(q * len(v)) - 1, 0)]


@dataclasses.dataclass
class Context:
    records: list                 # generator.Record of the window
    setup_s: float
    spans: Optional[list] = None  # obs spans of the window, perf_counter
    trace: Optional[object] = None   # trace.DeviceTrace
    work: Optional[dict] = None   # kernel -> (flops, bytes) per request
    peak: Optional[dict] = None
    base: str = manifest.HERE
    _cache: dict = dataclasses.field(default_factory=dict)

    def value(self, name: str):
        """Another metric's reading (each is read once)."""
        if name not in self._cache:
            self._cache[name] = manifest.metric(name, self.base).read(self)
        return self._cache[name]

    def latencies(self) -> list[float]:
        return [r.latency for r in self.records]

    def served(self) -> int:
        return sum(r.done is not None for r in self.records)

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans or () if s.name == name]

    def per_request_s(self, name: str) -> Optional[float]:
        """Seconds of ``name`` spans per request span, or None when the
        window holds no request span."""
        n = len(self.spans_named("request"))
        if not n:
            return None
        return sum(s.duration for s in self.spans_named(name)) / n

    def kernel_device_s(self, pattern: str) -> Optional[float]:
        """Device seconds per served request in the jitted programs whose
        name matches ``pattern``; None without a trace or such programs."""
        if self.trace is None or not self.served():
            return None
        s = self.trace.module_seconds(pattern)
        return s / self.served() if s > 0 else None

    def roofline_pct(self, kernel: str, device_s: Optional[float]
                     ) -> Optional[float]:
        from chipbench.work import least_time_s
        if (not device_s or not self.work or self.peak is None
                or kernel not in self.work):
            return None
        flops, nbytes = self.work[kernel]
        return 100.0 * least_time_s(flops, nbytes, self.peak)[0] / device_s

    def host_activity(self, a: float, b: float) -> dict:
        """Seconds of the trace interval [a, b] by what the program was
        doing, by its obs spans: the innermost span open at each instant,
        or "no request in service"."""
        if "spans" not in self._cache:
            sp = self.spans or []
            t0 = np.array([self.trace.to_trace(s.t0) for s in sp])
            self._cache["spans"] = (t0, t0 + np.array(
                [s.duration for s in sp]), [s.name for s in sp])
        t0s, t1s, names = self._cache["spans"]
        near = np.flatnonzero((t0s < b) & (t1s > a))
        cuts = sorted({a, b, *(t for i in near for t in (t0s[i], t1s[i])
                               if a < t < b)})
        out: dict[str, float] = {}
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            open_ = [(t1s[i] - t0s[i], names[i]) for i in near
                     if t0s[i] <= mid <= t1s[i]]
            label = min(open_)[1] if open_ else "no request in service"
            out[label] = out.get(label, 0.0) + (hi - lo)
        return out


def breakdown(ctx: Context, top: int = 10) -> Optional[dict]:
    """The device operations that took most time, and the device's idle
    time summed by what the host was doing meanwhile."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    ops = sorted(ctx.trace.op_seconds().items(), key=lambda kv: -kv[1])
    idle: dict[str, float] = {}
    for a, b in ctx.trace.idle_gaps():
        for label, sec in ctx.host_activity(a, b).items():
            idle[label] = idle.get(label, 0.0) + sec
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def spans_on_perf_clock(spans: list, anchor_perf_s: float) -> list:
    """Obs spans with ``t0`` moved from the tracer's epoch onto
    ``perf_counter``, using the anchor span opened at ``anchor_perf_s``;
    the anchor itself is dropped."""
    anchor = [s for s in spans if s.name == SPAN_ANCHOR]
    if not anchor:
        raise ValueError("no anchor span")
    shift = anchor_perf_s - anchor[0].t0
    return [dataclasses.replace(s, t0=s.t0 + shift) for s in spans
            if s.name != SPAN_ANCHOR]
