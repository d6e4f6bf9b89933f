"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window
(profiler trace)."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return (1.0 - run.trace["busy_s"] / run.trace["window_s"]) * 100.0
