"""Scheduler and admission: the mean time, per decode step, from the engine
step's start to the decode program's enqueue — migrations poll, lane fill
(prefill and restore writes into the pools), page checks, input upload and
dispatch (the engine's ``decode.prep_s`` histogram), in ms."""

HISTOGRAM = "decode.prep_s"


def read(rec):
    h = rec.histograms.get(HISTOGRAM)
    return 1e3 * h["sum"] / h["count"] if h and h["count"] else None
