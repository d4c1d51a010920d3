"""The program's own spans: the `engine.*` TraceAnnotations that
`serving/engine.py` opens inside each engine call, read from the traced
run's profile.

They sit on the host plane of the same profile as the device operations,
so they share the device planes' clock (nanoseconds). A span's metadata
(`uid`, `slot`, `prompt_len`, `n`, `live`) arrives as the event's stats.

`spans(run)` reads the newest `.xplane.pb` under harness.TRACE_DIR, which
run_cell leaves in place, once per Run, and caches the span trees on it
(`run.engine_spans`); a test sets that attribute directly. A run without
a device trace, or a program without these spans (a commit before them),
gives no spans, and every reader built on them returns None.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import harness
import reduce_trace as trace_red

PREFIX = "engine."


@dataclass
class Span:
    name: str
    start: float
    end: float
    meta: Dict[str, object] = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.end - self.start

    def kids(self, name: str) -> List["Span"]:
        return [c for c in self.children if c.name == name]

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def build(events: Sequence[Tuple[str, float, float, dict]]) -> List[Span]:
    """Span trees from one thread's events (name, start, end, meta): a
    span is the child of the innermost span that contains it."""
    roots: List[Span] = []
    stack: List[Span] = []
    for name, s, e, meta in sorted(events, key=lambda x: (x[1], -x[2])):
        sp = Span(name, s, e, dict(meta))
        while stack and stack[-1].end < e:
            stack.pop()
        (stack[-1].children if stack else roots).append(sp)
        stack.append(sp)
    return roots


def read_profile(log_dir) -> List[Span]:
    """The `engine.*` span trees of the newest profile under log_dir, in
    start order; [] where there is no profile."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(str(log_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    roots: List[Span] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = []
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    events.append((ev.name, ev.start_ns, ev.end_ns,
                                   dict(ev.stats)))
            roots += build(events)
    return sorted(roots, key=lambda sp: sp.start)


def spans(run) -> List[Span]:
    """The run's `engine.*` span trees; [] without a device trace."""
    if not hasattr(run, "engine_spans"):
        run.engine_spans = (read_profile(harness.TRACE_DIR)
                            if run.has_device_trace() else [])
    return run.engine_spans


def in_window(run, name: Optional[str] = None) -> List[Span]:
    """Top-level spans lying wholly inside the traced window."""
    if not run.has_device_trace():
        return []
    lo, hi = run.trace.window()
    return [sp for sp in spans(run) if sp.start >= lo and sp.end <= hi
            and (name is None or sp.name == name)]


def host_ms(sp: Span) -> float:
    """The span less its `engine.wait` children: the host's own work."""
    return (sp.dur - sum(w.dur for w in sp.kids("engine.wait"))) / 1e6


def exposed(run) -> List[trace_red.Interval]:
    """Where the host is inside an `engine.*` span and not waiting on the
    device, clipped to the traced window."""
    lo, hi = run.trace.window()
    inside = trace_red.merge([(max(sp.start, lo), min(sp.end, hi))
                              for sp in spans(run)])
    waits = trace_red.merge([(max(w.start, lo), min(w.end, hi))
                             for sp in spans(run) for w in sp.walk()
                             if w.name == "engine.wait"])
    return subtract(inside, waits)


def subtract(a: Sequence[trace_red.Interval],
             b: Sequence[trace_red.Interval]) -> List[trace_red.Interval]:
    """a minus b, both sorted and disjoint."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out
