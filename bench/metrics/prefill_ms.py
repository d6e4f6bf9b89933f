"""Device time of the engine's prefill program per launch, from the
profiler trace. ``DecodeEngine`` names its jitted prefill ``prefill``, so
the module is ``jit_prefill``; a program whose prefill has another name
reads nothing."""

MODULE = "jit_prefill"


def read(run):
    if run.trace is None:
        return None
    m = run.trace["modules"].get(MODULE)
    if not m or not m["launches"]:
        return None
    return m["seconds"] / m["launches"] * 1e3
