"""Monitor: host time of the harness's calls to ``_flush_telemetry`` (the
telemetry plane and the DPU sidecar), over the decode steps."""

from benchmarks.chip.stats import window_steps


def read(run):
    steps = len(window_steps(run.record))
    return run.record.flush_s / steps * 1e3 if steps else None
