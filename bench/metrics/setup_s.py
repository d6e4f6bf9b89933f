"""Process start to the first timed request: imports, weights, the
engine's warm-up and any compilation (host clock)."""


def read(run):
    return run.setup_s
