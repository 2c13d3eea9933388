"""Output tokens first seen inside the window, over the window's seconds."""


def read(rec):
    n = sum(1 for r in rec.recs for t in r.times if rec.t_start < t <= rec.t_stop)
    return n / (rec.t_stop - rec.t_start)
