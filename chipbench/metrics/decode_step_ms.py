"""Model step: device time of one decode program execution, the mean over
the executions inside the traced window (the jitted ``_decode_impl``)."""

PROGRAM = "_decode_impl"


def read(rec):
    if rec.trace is None:
        return None
    n, ns = rec.trace.module(PROGRAM)
    return ns / n * 1e-6 if n else None
