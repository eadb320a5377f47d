"""Model step, prefill: device time of the prefill programs in the trace
(the engine's ``prefill_one``), per call."""

PROGRAM = "prefill_one"


def read(run):
    if run.trace is None:
        return None
    sec, calls = run.trace.program(PROGRAM)
    return sec / calls * 1e3 if calls else None
