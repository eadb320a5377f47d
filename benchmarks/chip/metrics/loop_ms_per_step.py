"""Engine host loop: the traced window over the decode steps taken in it."""

from benchmarks.chip.stats import window_steps


def read(run):
    if run.trace is None:
        return None
    steps = len(window_steps(run.record))
    return run.trace.window_s / steps * 1e3 if steps else None
