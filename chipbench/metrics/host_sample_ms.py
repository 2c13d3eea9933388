"""Host sampling: the mean time, per decode step, from the decode program's
logits being ready on the device to the last lane's token chosen — their
copy to the host and cast, the per-lane choice and retirement (the engine's
``decode.sample_s`` histogram), in ms."""

HISTOGRAM = "decode.sample_s"


def read(rec):
    h = rec.histograms.get(HISTOGRAM)
    return 1e3 * h["sum"] / h["count"] if h and h["count"] else None
