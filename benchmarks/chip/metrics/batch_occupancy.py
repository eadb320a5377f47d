"""Scheduler: mean number of running slots per decode step in the window."""

from benchmarks.chip.stats import window_steps


def read(run):
    steps = [s for s in window_steps(run.record)
             if s.end <= run.record.seconds]
    return sum(len(s.contexts) for s in steps) / len(steps) if steps \
        else None
