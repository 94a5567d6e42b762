"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

Device operations are the events of each device plane's ``XLA Ops``
line, and the jitted programs they belong to those of its ``XLA
Modules`` line, named after the jitted function (``jit_<name>(<id>)``):
a kernel is found by the name of the function that launches it. The
program's obs spans and the harness's clock are ``time.perf_counter``
readings; an anchor annotation, whose ``perf_counter`` reading the
harness records as it opens it, puts them on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

ANCHOR = "chipbench.anchor"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
_HLO = re.compile(r"^%?([\w.\-]+) = .*?\s([\w\-]+)\(")


@dataclasses.dataclass
class DeviceTrace:
    """Device operations of one traced window, on the trace clock (s).

    ``ops[d]`` is an ``(n, 2)`` array of [start, end] of device ``d``'s
    operations and ``names[d]`` their names; ``modules``/``module_names``
    the same for its jitted programs. ``offset`` maps a ``perf_counter``
    reading onto the trace clock (add it)."""

    ops: list
    names: list
    offset: float
    modules: list = dataclasses.field(default_factory=list)
    module_names: list = dataclasses.field(default_factory=list)
    window: tuple[float, float] = (0.0, 0.0)

    def to_trace(self, perf_s: float) -> float:
        return perf_s + self.offset

    def clipped(self, d: int) -> np.ndarray:
        t0, t1 = self.window
        iv = np.clip(self.ops[d], t0, t1)
        return iv[iv[:, 1] > iv[:, 0]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([union_s(self.clipped(d))
                              for d in range(len(self.ops))]))

    def op_seconds(self, pattern: str | None = None, *,
                   modules: bool = False) -> dict:
        """Device seconds by operation name (or with ``modules``, by
        jitted program) in the window, summed over devices and divided by
        their number; with ``pattern``, only the names it matches
        (``re.search``)."""
        rx = re.compile(pattern) if pattern else None
        out: dict[str, float] = {}
        t0, t1 = self.window
        pairs = (zip(self.modules, self.module_names) if modules
                 else zip(self.ops, self.names))
        for iv, names in pairs:
            dur = np.clip(iv[:, 1], t0, t1) - np.clip(iv[:, 0], t0, t1)
            for name, s in zip(names, dur):
                if s > 0 and (rx is None or rx.search(name)):
                    out[name] = out.get(name, 0.0) + float(s)
        n = max(len(self.ops), 1)
        return {k: v / n for k, v in out.items()}

    def module_seconds(self, pattern: str) -> float:
        """Device seconds in the window of the jitted programs whose name
        matches ``pattern``, averaged over devices."""
        return sum(self.op_seconds(pattern, modules=True).values())

    def idle_gaps(self) -> list[tuple[float, float]]:
        """[start, end] of each stretch of the window in which device 0
        ran nothing."""
        if not self.ops:
            return []
        t0, t1 = self.window
        merged = merge(self.clipped(0))
        edges = np.concatenate([[t0], merged.ravel(), [t1]]).reshape(-1, 2)
        return [(float(a), float(b)) for a, b in edges if b > a]


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of intervals as sorted, disjoint [start, end] rows."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])
    return np.stack([starts, ends[last]], axis=1)


def union_s(iv: np.ndarray) -> float:
    m = merge(iv)
    return float((m[:, 1] - m[:, 0]).sum())


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_planes(path: str) -> list[dict]:
    """The trace as plain data: ``[{"name", "lines": [{"name",
    "events": [(name, start_ns, duration_ns)]}]}]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(ev.name, ev.start_ns, ev.duration_ns)
                                   for ev in ln.events]}
                       for ln in p.lines]}
            for p in pd.planes]


def short_op_name(hlo: str, module: str) -> str:
    """``<program>/<instruction> <opcode>`` from an op event's HLO text,
    e.g. ``jit_cluster_spmm_compact/closed_call.13 custom-call``."""
    m = _HLO.match(hlo)
    op = f"{m.group(1)} {m.group(2)}" if m else hlo[:80]
    return f"{module.split('(')[0]}/{op}" if module else op


def _intervals(events) -> np.ndarray:
    return np.array([(s, s + d) for _, s, d in events],
                    dtype=np.float64).reshape(-1, 2) * 1e-9


def reduce_planes(planes: list[dict], anchor_perf_s: float) -> DeviceTrace:
    """Device operations of ``planes`` with the offset that maps
    ``perf_counter`` onto their clock, from the anchor annotation that
    was opened at ``anchor_perf_s``."""
    anchor = None
    out = DeviceTrace(ops=[], names=[], offset=0.0)
    for p in planes:
        device = bool(DEVICE_PLANE.match(p["name"]))
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        if device and OP_LINE in lines:
            ops = _intervals(lines[OP_LINE])
            mods = lines.get(MODULE_LINE, [])
            miv = _intervals(mods)
            # the program each operation ran in: the last one started
            at = np.searchsorted(miv[:, 0], ops[:, 0], side="right") - 1
            out.ops.append(ops)
            out.names.append([
                short_op_name(n, mods[i][0] if i >= 0 else "")
                for (n, _, _), i in zip(lines[OP_LINE], at)])
            out.modules.append(miv)
            out.module_names.append([n.split("(")[0] for n, _, _ in mods])
        for ln in p["lines"]:
            if anchor is None and not device:
                for n, s, _ in ln["events"]:
                    if n == ANCHOR:
                        anchor = s * 1e-9
                        break
    if anchor is None:
        raise ValueError(f"trace holds no {ANCHOR} annotation")
    out.offset = anchor - anchor_perf_s
    return out
