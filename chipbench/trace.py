"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, time per
XLA program and per operation, and the device's idle gaps named by what the
benchmark's host spans were doing.

On a TPU the profiler writes one plane per chip (``/device:TPU:<n>``) with an
``XLA Modules`` line (one event per program run) and an ``XLA Ops`` line (one
event per operation, Mosaic kernels among them).  A run on the CPU (the
tests) has no such plane; there, and only when asked, the operations are the
host plane's events that carry an ``hlo_module`` stat.  Host spans are the events named ``chipbench.*``, which
the drivers put around ``submit_*`` and the waits for answers.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start, end in seconds on the trace's clock
#: The host span that marks the traced sub-window itself.
WINDOW_SPAN = "chipbench.window"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted intervals covering the same time."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


@dataclass
class Event:
    name: str
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class Trace:
    """What one traced sub-window holds, per device and on the host."""

    ops: Dict[str, List[Event]]  # device -> operation events
    modules: Dict[str, List[Event]]  # device -> program events
    spans: List[Event]  # the benchmark's host spans
    lo: float  # the sub-window on the trace's clock
    hi: float

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(
            covered(clip([(e.start, e.end) for e in evs], self.lo, self.hi)) for evs in self.ops.values()
        ) / len(self.ops)

    def module_seconds(self) -> Dict[str, float]:
        """Program name -> device seconds (summed over devices and runs)."""
        out: Dict[str, float] = {}
        for evs in self.modules.values():
            for e in evs:
                s, t = max(e.start, self.lo), min(e.end, self.hi)
                if t > s:
                    out[e.name] = out.get(e.name, 0.0) + (t - s)
        return out

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest intervals with no operation on the first device,
        each named by the host span that overlaps it most."""
        if not self.ops:
            return []
        busy = union(clip([(e.start, e.end) for e in self.ops[self.devices[0]]], self.lo, self.hi))
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, best_t = "no benchmark span", 0.0
            per: Dict[str, float] = {}
            for sp in self.spans:
                t = min(e, sp.end) - max(s, sp.start)
                if t > 0:
                    per[sp.name] = per.get(sp.name, 0.0) + t
            for name, t in per.items():
                if t > best_t:
                    best, best_t = name, t
            out.append((best, e - s))
        return out


_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8)\[([0-9,]*)\]")


def custom_call_shapes(hlo: str):
    """(output, [operands]) as (dtype, shape) pairs of a Mosaic kernel's
    event, whose name is its HLO instruction; None for any other event."""
    if 'custom_call_target="tpu_custom_call"' not in hlo or "custom-call(" not in hlo:
        return None
    head, args = hlo.split("custom-call(", 1)
    args = args.split("custom_call_target=", 1)[0]  # layouts hold parentheses of their own

    def shapes(text):
        return [(dt, tuple(int(d) for d in dims.split(",") if d)) for dt, dims in _SHAPE.findall(text)]

    out = shapes(head.split("=", 1)[-1])
    return (out[0], shapes(args)) if out else None


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # an event without readable stats
        return {}


def find(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def read(path: str, lo_ns: Optional[float] = None, hi_ns: Optional[float] = None,
         host_ops: bool = False) -> Trace:
    """Parse one ``.xplane.pb``.  ``lo_ns``/``hi_ns`` bound the sub-window on
    the trace's clock; by default the ``chipbench.window`` span does, or else
    the first and last event.  Only with ``host_ops`` (a run on the CPU) do
    the host's XLA operations stand for the device's; otherwise a trace with
    no accelerator plane has no operations, and the device metrics read
    nothing."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    on_host: List[Event] = []
    for plane in pd.planes:
        name = plane.name
        device = name.startswith("/device:TPU")
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = Event(ev.name, s, s + ev.duration_ns * 1e-9)
                if device and line.name == "XLA Ops":
                    e.stats = _stats(ev)
                    ops.setdefault(name, []).append(e)
                elif device and line.name == "XLA Modules":
                    modules.setdefault(name, []).append(e)
                elif name.startswith("/host"):
                    if ev.name.startswith("chipbench."):
                        spans.append(e)
                    elif ev.duration_ns > 0:
                        st = _stats(ev)
                        if "hlo_module" in st:
                            e.stats = st
                            on_host.append(e)
    if host_ops and not ops and on_host:  # a CPU run: the host's XLA operations
        ops["/host:CPU"] = on_host
        by_run: Dict[object, Event] = {}
        for e in on_host:
            key = (e.stats.get("hlo_module"), e.stats.get("run_id"))
            run = by_run.get(key)
            if run is None:
                by_run[key] = Event(str(e.stats.get("hlo_module")), e.start, e.end)
            else:
                run.start, run.end = min(run.start, e.start), max(run.end, e.end)
        modules["/host:CPU"] = list(by_run.values())
    window = [e for e in spans if e.name == WINDOW_SPAN]
    spans = [e for e in spans if e.name != WINDOW_SPAN]
    every = window or [e for evs in ops.values() for e in evs] + spans
    lo = lo_ns * 1e-9 if lo_ns is not None else min((e.start for e in every), default=0.0)
    hi = hi_ns * 1e-9 if hi_ns is not None else max((e.end for e in every), default=0.0)
    return Trace(ops=ops, modules=modules, spans=spans, lo=lo, hi=hi)
