"""Output tokens completed in the window, over the window (host clock)."""


def read(run):
    n = sum(1 for r in run.recs for t in r.times if run.in_window(t))
    return n / run.seconds if run.seconds > 0 else None
