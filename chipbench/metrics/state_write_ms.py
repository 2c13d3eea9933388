"""Scheduler and admission: the decode loop's mean time writing one lane's
recurrent state at its assignment (the engine's ``lanes.state_write_s``
histogram), in ms: the state rows sliced from the request's prefill tree
and the in-place row write enqueued.  A program without the histogram, or
a family without per-lane state, reads nothing."""

HISTOGRAM = "lanes.state_write_s"


def read(rec):
    h = rec.histograms.get(HISTOGRAM)
    return 1e3 * h["sum"] / h["count"] if h and h["count"] else None
