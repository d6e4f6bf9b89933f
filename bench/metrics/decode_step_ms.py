"""Device time of the engine's decode-step program per launch, from the
profiler trace (the module is named after the jitted step)."""


def read(run):
    if run.trace is None or run.decode_module is None:
        return None
    m = run.trace["modules"].get(run.decode_module)
    if not m or not m["launches"]:
        return None
    return m["seconds"] / m["launches"] * 1e3
