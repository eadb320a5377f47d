"""Arithmetic shared by the metric readers: tails over all requests, rates
over the whole window."""

from __future__ import annotations

import math
from dataclasses import dataclass

from benchmarks.chip.serve_loop import Record


@dataclass
class Run:
    """Everything a metric reader may read from one run."""
    record: Record
    setup_s: float
    config: dict
    peak: dict
    trace: object = None             # xtrace.TraceSummary in a traced run


def quantile(xs: list[float], q: float) -> float | None:
    """Linear interpolation between order statistics (numpy's default)."""
    if not xs:
        return None
    s = sorted(xs)
    pos = q * (len(s) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def ttfts(rec: Record) -> list[float]:
    """Due to first token, for every request due in the window.  One that
    never got its first token counts as waiting until the loop stopped."""
    return [(r.token_times[0] if r.tokens else rec.stop) - r.due
            for r in rec.requests if r.due < rec.seconds]


def token_gaps(rec: Record) -> list[float]:
    out = []
    for r in rec.requests:
        t = r.token_times
        out.extend(b - a for a, b in zip(t[:-1], t[1:]))
    return out


def tokens_in_window(rec: Record) -> int:
    return sum(1 for r in rec.requests for t in r.token_times
               if t <= rec.seconds)


def queue_waits(rec: Record) -> list[float]:
    return [r.prefill_start - r.due for r in rec.requests
            if r.prefill_start >= 0]


def window_steps(rec: Record):
    return [s for s in rec.steps if s.end <= rec.stop]
