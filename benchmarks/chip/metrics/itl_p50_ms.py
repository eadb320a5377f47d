"""Median over every gap between consecutive tokens of every request in the
window: the pace of a stream between prefills."""

from benchmarks.chip.stats import quantile, token_gaps


def read(run):
    q = quantile(token_gaps(run.record), 0.5)
    return None if q is None else q * 1e3
