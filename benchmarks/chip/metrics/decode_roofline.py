"""Kernels (the decode program): the least time of the decode steps'
work, max(operations / peak rate, bytes / peak bandwidth), counted from the
live lengths, over the decode program's device time, in percent."""

from benchmarks.chip import work
from benchmarks.chip.stats import window_steps

PROGRAM = "decode_step"


def read(run):
    if not run.peak:
        return None
    if run.trace is None:
        return None
    sec, calls = run.trace.program(PROGRAM)
    steps = window_steps(run.record)
    if not calls or not steps:
        return None
    least = [work.least_time(*work.decode_step(run.config, s.contexts),
                             run.peak) for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sec / calls)
