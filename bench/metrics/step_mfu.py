"""Whole-step model FLOP utilisation: output tokens per second x 2 x
the parameters a token multiplies (counts.flops_per_token) over the
device's bf16 peak. Prompt tokens and attention over the context are
not counted, so this is a lower bound."""
import counts


def read(run):
    if run.peak is None or run.seconds <= 0:
        return None
    n = sum(1 for r in run.recs for t in r.times if run.in_window(t))
    return n / run.seconds * counts.flops_per_token(run.dims) \
        / run.peak["bf16_flops"] * 100.0
