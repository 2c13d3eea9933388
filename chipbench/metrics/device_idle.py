"""Device: share of the traced window in which no operation ran on the
device (one minus the union of op intervals, averaged over the chips)."""


def read(rec):
    t = rec.trace
    if t is None or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)
