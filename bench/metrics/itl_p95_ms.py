"""95th percentile of every gap between consecutive output tokens of a
request, over the gaps that end in the window (host clock)."""
import numpy as np


def read(run):
    x = [(b - a) * 1e3 for r in run.recs
         for a, b in zip(r.times, r.times[1:])
         if a >= run.t0 and run.in_window(b)]
    return float(np.percentile(x, 95)) if x else None
