"""Per-request statistics of a run's window, shared by the metric readers
and the run's report. Times are host-clock seconds: a token is stamped
when the engine call that made it returns."""
from __future__ import annotations

from typing import List


def in_window(run, t: float) -> bool:
    return run.w0 <= t <= run.w1


def window_tokens(run) -> int:
    return sum(1 for r in run.requests for t in r.tokens if in_window(run, t))


def tpot_samples(run) -> List[float]:
    """For each request with at least two tokens inside the window: the
    time between its first and last in-window tokens over their count - 1."""
    out = []
    for r in run.requests:
        ts = [t for t in r.tokens if in_window(run, t)]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def due_in_window(run) -> list:
    return [r for r in run.requests if run.w0 <= r.due < run.w1]


def ttft_samples(run) -> List[float]:
    """From due time to first token, for every request due in the window;
    a request with no token by the window's end counts at the window's
    end."""
    out = []
    for r in due_in_window(run):
        first = r.tokens[0] if r.tokens else None
        out.append((first if first is not None and first <= run.w1
                    else run.w1) - r.due)
    return out


def attempted(run) -> list:
    """Requests due in the window, or with a token in it."""
    return [r for r in run.requests
            if run.w0 <= r.due < run.w1
            or any(in_window(run, t) for t in r.tokens)]
