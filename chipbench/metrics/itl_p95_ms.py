"""95th percentile of the gaps between consecutive output tokens of a
request, as the host sees them, over all gaps inside the window."""
import numpy as np


def read(rec):
    gaps = [b - a for r in rec.recs for a, b in zip(r.times, r.times[1:])
            if rec.t_start < a and b <= rec.t_stop]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
