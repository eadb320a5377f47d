"""Scheduler: 90th percentile of the time from when a request was due to
the start of its admission (its prefill call), over admitted requests."""

from benchmarks.chip.stats import quantile, queue_waits


def read(run):
    q = quantile(queue_waits(run.record), 0.9)
    return None if q is None else q * 1e3
