"""90th percentile, over every request due in the window, of the time from
when it was due until its first token (the prefill's) was on the host."""

from benchmarks.chip.stats import quantile, ttfts


def read(run):
    q = quantile(ttfts(run.record), 0.9)
    return None if q is None else q * 1e3
