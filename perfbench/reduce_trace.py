"""Reduction from a profiler trace and the harness's host spans to device
busy time, attributed to the host call in which each device operation ran.

The harness wraps each engine call in a `jax.profiler.TraceAnnotation`
named `pb.<kind>#<index>` (kind: wave, refill, decode) and the measured
window in `pb.window`. Device operations are attributed to the host span
that contains them, not by program name, so a program that a later change
renames or splits still lands in the right layer. Each call ends by
syncing its sampled tokens to the host, so its device work lies inside
its span.

Intervals are (start, end) pairs in one time base (nanoseconds in a
trace).
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "pb."


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Busy:
    """Union of device-operation intervals, queried over host spans."""

    def __init__(self, intervals: Sequence[Interval]):
        self.iv = merge(intervals)
        self.starts = [s for s, _ in self.iv]
        self.prefix = [0.0]
        for s, e in self.iv:
            self.prefix.append(self.prefix[-1] + (e - s))

    def _upto(self, t: float) -> float:
        """Busy time in (-inf, t]."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.iv[i - 1]
        return self.prefix[i - 1] + (min(e, t) - s)

    def within(self, lo: float, hi: float) -> float:
        return max(0.0, self._upto(hi) - self._upto(lo)) if hi > lo else 0.0

    def gaps(self, lo: float, hi: float) -> List[Interval]:
        """Idle intervals inside [lo, hi]."""
        out, t = [], lo
        i = max(0, bisect.bisect_right(self.starts, lo) - 1)
        for s, e in self.iv[i:]:
            if s >= hi:
                break
            if s > t:
                out.append((t, min(s, hi)))
            t = max(t, e)
        if t < hi:
            out.append((t, hi))
        return out


def idle_by_span(busy: Busy, spans: Sequence[Tuple[str, float, float]],
                 lo: float, hi: float) -> Dict[str, float]:
    """Idle device time in [lo, hi], by the kind of host span it fell in
    ('between_calls' where no span was open). Spans must not overlap."""
    spans = sorted(spans, key=lambda x: x[1])
    ends = [e for _, _, e in spans]
    out: Dict[str, float] = {}
    for g0, g1 in busy.gaps(lo, hi):
        covered = 0.0
        i = bisect.bisect_right(ends, g0)
        while i < len(spans) and spans[i][1] < g1:
            name, s, e = spans[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                key = span_kind(name)
                out[key] = out.get(key, 0.0) + overlap
                covered += overlap
            i += 1
        if g1 - g0 - covered > 0:
            out["between_calls"] = (out.get("between_calls", 0.0)
                                    + (g1 - g0 - covered))
    return out


def span_kind(name: str) -> str:
    """'pb.decode#12' -> 'decode'."""
    return name[len(SPAN_PREFIX):].split("#", 1)[0]


def span_index(name: str) -> int:
    return int(name.split("#", 1)[1])


@dataclass
class TraceData:
    """What the reduction needs of one profile, in nanoseconds."""
    device_ops: List[List[Tuple[str, float, float]]] = field(
        default_factory=list)           # per device plane: (name, start, end)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def window(self) -> Interval:
        for name, s, e in self.spans:
            if name == SPAN_PREFIX + "window":
                return s, e
        raise ValueError("trace holds no pb.window span")

    def calls(self) -> List[Tuple[str, float, float]]:
        return [x for x in self.spans
                if x[0] != SPAN_PREFIX + "window"]


def top_ops(ops: Sequence[Tuple[str, float, float]], lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """The n operation names with most self time inside [lo, hi]. An
    operation nested in another (a fusion inside a loop) is subtracted
    from the one around it, so no time counts twice."""
    tot: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []          # (name, end) of open ops
    for name, s, e in sorted((x for x in ops if x[1] >= lo and x[2] <= hi),
                             key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            tot[parent] = tot.get(parent, 0.0) - (e - s)
        tot[name] = tot.get(name, 0.0) + (e - s)
        stack.append((name, e))
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def op_name(module: str, hlo: str) -> str:
    """'jit_f(123)', '%fusion.3 = bf16[...] fusion(...)' -> 'jit_f(123):fusion.3'."""
    return f"{module}:{hlo.split(' = ', 1)[0].lstrip('%')}"


def read_profile(log_dir: str) -> TraceData:
    """Read the newest `.xplane.pb` under log_dir. Device planes are
    `/device:<accelerator>:<n>`; their 'XLA Ops' line holds the operations,
    named '<module>:<op>' from the 'XLA Modules' line. Host spans are the
    TraceAnnotations whose names start with 'pb.'."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profile under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    data = TraceData()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                          for ev in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            mstarts = [m[0] for m in mods]
            ops = []
            for ev in lines["XLA Ops"].events:
                s, e = ev.start_ns, ev.end_ns
                i = bisect.bisect_right(mstarts, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
                ops.append((op_name(mod, ev.name), s, e))
            data.device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        data.spans.append((ev.name, ev.start_ns, ev.end_ns))
    data.spans.sort(key=lambda x: x[1])
    return data
