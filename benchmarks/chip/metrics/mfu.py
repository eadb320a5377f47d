"""Whole step: model operations done in the window (the architecture's
count: 2 x N per prompt token and per generated token, plus attention over
the live context) over the window's seconds times the bf16 peak of the
chips in use, in percent."""


def read(run):
    if not run.peak:
        return None
    rec = run.record
    flops = 0.0
    for r in rec.requests:
        done = sum(1 for t in r.token_times if t <= rec.seconds)
        if done:
            flops += run.arch.request_flops(run.config, len(r.prompt), done)
    peak = rec.seconds * run.chips * run.peak["bf16_flops_per_s"]
    return 100.0 * flops / peak if flops else None
