"""Kernels (the decode program): the least time of the decode steps'
work, max(operations / peak rate, bytes / peak bandwidth), counted from the
live lengths by the configuration's architecture, over the decode
program's device time, in percent.  A step runs on one replica's chip, so
both sides are per chip: the mean least time of a step over the mean
device time of a call, over the steps and calls of every chip."""

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
    least = [work.least_time(*run.arch.decode_step(run.config, s), run.peak)
             for s in steps]
    return 100.0 * (sum(least) / len(least)) / (sec / calls)
