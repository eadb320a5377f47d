"""Model step, decode: device time of the decode program in the trace (the
engine's vmapped ``decode_step``), per call."""

PROGRAM = "decode_step"


def read(run):
    if run.trace is None:
        return None
    sec, calls = run.trace.program(PROGRAM)
    return sec / calls * 1e3 if calls else None
