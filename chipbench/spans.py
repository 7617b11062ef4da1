"""The serving program's own host spans (``costream.*``) beside the device.

``PlacementService`` and ``CostEstimator`` open ``jax.profiler`` spans around
each piece of host work (submit, the worker's wait, launch with featurize and
dispatch, finalize with fetch).  The profiler writes them on the host plane of
the same ``.xplane.pb`` that ``chipbench/trace.py`` reads, on the device
trace's clock.  ``read`` returns what ``trace.read`` returns, unchanged, plus
those spans, each with its arguments and the index of its thread's line in
the host plane: ``ProfileData`` names lines by thread name, which two threads
may share, and exposes no line id.

The per-layer readers ``chipbench/metrics/{submit,worker,featurize,dispatch,
fetch,finalize}_ms.py`` read a ``SpanTrace``; on a plain ``Trace`` they read
nothing.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import trace as T

PREFIX = "costream."
#: The span the drain worker holds while it waits for requests.
WORKER_WAIT = "costream.worker.wait"


@dataclass
class Span(T.Event):
    line: int = 0  # index of its thread's line in the host plane


@dataclass
class SpanTrace(T.Trace):
    program_spans: List[Span] = field(default_factory=list)

    def _by_line(self) -> Dict[int, List[Span]]:
        out: Dict[int, List[Span]] = {}
        for sp in self.program_spans:
            out.setdefault(sp.line, []).append(sp)
        return out

    def span_seconds(self, names: Iterable[str], self_time: bool = False) -> float:
        """Thread-seconds inside the named program spans, clipped to the
        sub-window (spans that nest on one line count once).  ``self_time``
        leaves out the part covered by other program spans nested inside them
        on the same line."""
        names = set(names)
        total = 0.0
        for spans in self._by_line().values():
            named = T.union([(s.start, s.end) for s in spans if s.name in names])
            total += T.covered(T.clip(named, self.lo, self.hi))
            if self_time and named:
                starts = [s for s, _ in named]
                inner = []
                for sp in spans:
                    if sp.name in names:
                        continue
                    i = bisect.bisect_right(starts, sp.start) - 1
                    if i >= 0 and sp.end <= named[i][1]:
                        inner.append((sp.start, sp.end))
                total -= T.covered(T.clip(inner, self.lo, self.hi))
        return total

    def worker_line(self) -> Optional[int]:
        """The line of the drain worker: the one that holds its wait spans."""
        lines = {sp.line for sp in self.program_spans if sp.name == WORKER_WAIT}
        return lines.pop() if len(lines) == 1 else None

    def line_shares(self, line: int) -> Dict[str, float]:
        """Share of the sub-window that one line spends in each outermost
        program span, by name, and in none (``"no span"``)."""
        spans = sorted((s for s in self.program_spans if s.line == line), key=lambda s: (s.start, -s.end))
        out: Dict[str, float] = {}
        end = float("-inf")
        for sp in spans:
            if sp.start >= end:  # outermost: not inside the previous outermost span
                for s, e in T.clip([(sp.start, sp.end)], self.lo, self.hi):
                    out[sp.name] = out.get(sp.name, 0.0) + (e - s)
                end = sp.end
        window = self.window_s()
        if window <= 0:
            return {}
        out = {k: v / window for k, v in out.items()}
        out["no span"] = 1.0 - sum(out.values())
        return out

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest intervals with no operation on the first device, each
        named by the program span that overlaps it most (ties to the shorter,
        innermost span), else by the benchmark's span that does, else
        ``"no benchmark span"``."""
        if not self.ops:
            return []
        busy = T.union(T.clip([(e.start, e.end) for e in self.ops[self.devices[0]]], self.lo, self.hi))
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, key = None, (0.0, 0.0)
            for sp in self.program_spans:
                t = min(e, sp.end) - max(s, sp.start)
                if t > 0 and (t, sp.start - sp.end) > key:
                    best, key = sp.name, (t, sp.start - sp.end)
            if best is None:
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


def read(path: str, lo_ns: Optional[float] = None, hi_ns: Optional[float] = None,
         host_ops: bool = False) -> SpanTrace:
    """``trace.read`` of ``path``, plus the host events named ``costream.*``."""
    from jax.profiler import ProfileData

    base = T.read(path, lo_ns, hi_ns, host_ops)
    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append(Span(ev.name, s, s + ev.duration_ns * 1e-9, T._stats(ev), i))
    return SpanTrace(**vars(base), program_spans=spans)


def per_request_ms(ctx, names: Iterable[str], self_time: bool = False) -> Optional[float]:
    """Seconds in the named program spans inside the traced sub-window, per
    request the service popped in it, in ms; None where there is nothing to
    read (no program spans, as from a plain ``Trace``, or no request)."""
    tr = ctx.trace
    n = (ctx.traced_stats or {}).get("n_drained")
    if not isinstance(tr, SpanTrace) or not n:
        return None
    seconds = tr.span_seconds(names, self_time)
    return 1e3 * seconds / n if seconds > 0 else None
