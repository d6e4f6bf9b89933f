"""The reduction from a profiler trace to device time, on a short trace
recorded on one TPU v5e (a jitted matmul and a reduction, five steps,
with host spans between them)."""
import os

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_probe.xplane.pb")


def test_union_and_clip():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)])
    assert u == [(0, 3), (5, 9), (10, 11)]
    assert trace_reduce.clip(u, 2, 10.5) == [(2, 3), (5, 9), (10, 10.5)]


def test_names_lose_their_hashes_and_operands():
    assert trace_reduce.module_base("jit__lambda(7604566414771)") == \
        "jit__lambda"
    assert trace_reduce.op_base("%fusion.3 = bf16[64]{0} fusion(...)") \
        == "fusion.3"


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(DATA)


def test_recorded_trace_reduces(trace):
    assert list(trace["devices"]) == ["/device:TPU:0"]
    spans = [s for s in trace["spans"] if s[0] != "bench.traced"]
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    r = trace_reduce.reduce(trace, (lo, hi))
    assert r["modules"]["jit_step_fn"]["launches"] == 5
    per_step = r["modules"]["jit_step_fn"]["seconds"] / 5
    assert 10e-6 < per_step < 30e-6           # a 2048x2048 bf16 read
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    # the device waits mostly while the host sleeps between steps
    assert r["idle_gaps"][0][0] == "bench.host_wait"
    assert r["device_ops"][0][0] == "copy-done"


def test_device_clock_is_aligned_to_the_host(trace):
    """Each launch ends on the device before the host learns of it."""
    assert trace["shift_ns"] > 0
    dev = trace["devices"]["/device:TPU:0"]
    steps = [s for s in trace["spans"] if s[0] == "bench.decode"]
    mods = [m for m in dev["modules"] if m[0] == "jit_step_fn"][-5:]
    for (_, s, e), (_, ms, me) in zip(steps, mods):
        assert s <= ms and me <= e


def test_a_trace_without_its_window_is_refused(trace):
    with pytest.raises(ValueError, match="bench.traced"):
        trace_reduce.reduce(trace)
