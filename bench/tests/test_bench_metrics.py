"""The reader of ``prefill_ms`` on a synthetic run: device time per
launch of the engine's prefill program, and nothing from a trace that
lacks that program (a program whose prefill has another name)."""
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import harness

TRACE = {"modules": {"jit_prefill": {"seconds": 0.09, "launches": 3},
                     "jit_decode_step": {"seconds": 0.06, "launches": 2}}}


def _run(trace):
    run = harness.Run(cell=None, dims={})
    run.trace = trace
    return run


def test_prefill_ms_reads_the_trace():
    assert harness.read_metric("prefill_ms", _run(TRACE)) == \
        pytest.approx(30.0)


@pytest.mark.parametrize("trace", [
    None,
    {"modules": {"jit_decode_step": {"seconds": 0.06, "launches": 2}}},
    {"modules": {"jit_fn": {"seconds": 0.09, "launches": 3}}},
    {"modules": {"jit_prefill": {"seconds": 0.0, "launches": 0}}}],
    ids=["untraced", "no_launch", "unnamed_prefill", "zero_launches"])
def test_prefill_ms_reads_nothing_without_its_module(trace):
    assert harness.read_metric("prefill_ms", _run(trace)) is None
