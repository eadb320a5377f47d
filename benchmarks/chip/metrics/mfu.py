"""Whole step: model operations done in the window (2 x N per prompt token
and per generated token, plus attention over the live context) over the
window's seconds times the chip's bf16 peak, in percent."""

from benchmarks.chip import work


def read(run):
    if not run.peak:
        return None
    rec = run.record
    flops = 0.0
    for r in rec.requests:
        done = sum(1 for t in r.token_times if t <= rec.seconds)
        if done:
            flops += work.request_flops(run.config, len(r.prompt), done)
    peak = rec.seconds * run.peak["bf16_flops_per_s"]
    return 100.0 * flops / peak if flops else None
