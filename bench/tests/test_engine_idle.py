"""The split of a traced window's device idle by the decode engine's
phases (``engine_idle.py``): an exact partition on a synthetic trace,
all of it ``outside`` on a recorded trace that holds no engine spans,
and the per-launch numbers."""
import glob
import os

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import engine_idle
import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_probe.xplane.pb")


def _us(*spans):
    return [(n, s * 1e3, e * 1e3) for n, s, e in spans]


def _engine_us(*spans):
    return [(n, s * 1e3, e * 1e3, a[0] if a else {})
            for n, s, e, *a in spans]


# device busy 0-10, 30-40, 60-65, 80-90 us of a 100 us window, with one
# prefill launch; two steps with nested refill spans, a stretch outside
# any step, and a third step that the window's end cuts
TRACE = {"devices": {"/device:TPU:0": {
             "ops": _us(("a", 0, 10), ("b", 30, 40), ("c", 60, 65),
                        ("d", 80, 90)),
             "modules": _us(("jit_prefill", 0, 10),
                            ("jit_decode_step", 30, 40))}},
         "spans": _us(("bench.traced", 0, 100))}
SPANS = _engine_us(
    ("engine.step", 5, 50),
    ("engine.refill", 12, 20),
    ("engine.prefill", 13, 16, {"bucket": 2, "prompt_len": 8}),
    ("engine.dispatch", 20, 22),
    ("engine.readback", 22, 35), ("engine.bookkeep", 36, 45),
    ("engine.step", 55, 85), ("engine.dispatch", 55, 58),
    ("engine.readback", 58, 70), ("engine.bookkeep", 72, 78),
    ("engine.step", 95, 110), ("engine.dispatch", 96, 98),
    ("engine.prefill", 96, 101, {"bucket": 1, "prompt_len": 8}))


def test_split_partitions_the_idle():
    sp = engine_idle.split(TRACE, SPANS)
    want = {"engine.refill": 8, "engine.dispatch": 7,
            "engine.readback": 15, "engine.bookkeep": 11,
            "engine.step": 14, "outside": 10}
    assert sp["idle_by_engine"] == pytest.approx(
        {k: v * 1e-6 for k, v in want.items()}, rel=1e-12)
    assert sum(sp["idle_by_engine"].values()) == pytest.approx(
        sp["window_s"] - sp["busy_s"], rel=1e-12)
    assert sp["engine_spans"] == {
        "engine.step": 2, "engine.refill": 1, "engine.prefill": 1,
        "engine.dispatch": 3, "engine.readback": 2, "engine.bookkeep": 2}
    # the one prefill inside the window scanned 7 positions
    assert (sp["prefill_positions"], sp["prefill_launches"],
            sp["prefill_s"]) == (7, 1, pytest.approx(10e-6))


@pytest.mark.parametrize("name, want", [
    ("refill_idle_ms", 8e-3 / 3), ("readback_idle_ms", 15e-3 / 3),
    ("loop_idle_ms", 32e-3 / 3), ("prefill_ms_per_position", 10e-3 / 7)])
def test_per_launch_reads_the_split(name, want):
    sp = engine_idle.split(TRACE, SPANS)
    assert engine_idle.per_launch_ms(sp)[name] == pytest.approx(want)


@pytest.mark.parametrize("drop, empty", [
    ("engine.dispatch", ["refill_idle_ms", "readback_idle_ms",
                         "loop_idle_ms"]),
    ("engine.prefill", ["prefill_ms_per_position"])],
    ids=["no_dispatch", "no_prefill_span"])
def test_per_launch_reads_nothing_without_its_spans(drop, empty):
    sp = engine_idle.split(TRACE, [s for s in SPANS if s[0] != drop])
    got = engine_idle.per_launch_ms(sp)
    assert [k for k, v in got.items() if v is None] == empty


def test_unpaired_prefill_launches_read_nothing():
    """A prefill span whose launch fell outside the window."""
    spans = SPANS + _engine_us(
        ("engine.prefill", 70, 71, {"bucket": 1, "prompt_len": 16}))
    sp = engine_idle.split(TRACE, spans)
    assert engine_idle.per_launch_ms(sp)["prefill_ms_per_position"] is None


def test_recorded_trace_is_all_outside():
    """The probe's program has no engine spans: all its idle lies
    outside any step, and the harness's own reduction is unchanged."""
    tr = trace_reduce.load(DATA)
    assert engine_idle.engine_spans(DATA) == []
    spans = [s for s in tr["spans"] if s[0] != "bench.traced"]
    window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    sp = engine_idle.split(tr, [], window)
    idle = sp["window_s"] - sp["busy_s"]
    assert sp["idle_by_engine"]["outside"] == pytest.approx(idle, rel=1e-9)
    assert sum(sp["idle_by_engine"].values()) == pytest.approx(idle,
                                                               rel=1e-9)
    assert sp["engine_spans"] == {} and sp["prefill_launches"] == 0


def test_interval_arithmetic():
    a, b = [(0, 10), (20, 30)], [(5, 8), (9, 25), (28, 40)]
    assert engine_idle.intersect(a, b) == [(5, 8), (9, 10), (20, 25),
                                           (28, 30)]
    assert engine_idle.subtract(a, b) == [(0, 5), (8, 9), (25, 28)]
    assert engine_idle.subtract(a, []) == a
    assert engine_idle.subtract(a, [(-5, 50)]) == []
    assert engine_idle.measure(a) == 20


def test_engine_spans_are_read_from_the_host_plane(tmp_path):
    import jax

    from repro import obs
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.profiled_span("engine.prefill", bucket=2, prompt_len=8):
            with jax.profiler.TraceAnnotation("bench.step"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = engine_idle.engine_spans(path[0])
    assert [(n, a) for n, _, _, a in spans] == \
        [("engine.prefill", {"bucket": 2, "prompt_len": 8})]
    assert spans[0][1] < spans[0][2]


def test_the_tool_leaves_the_reduction_as_it_was():
    """Off the chip ``run.py`` refuses the platform; the tool returns its
    code and puts ``trace_reduce.load`` back."""
    load = trace_reduce.load
    rc = engine_idle.main(["--workload", "internlm2_1_8b.decode_long",
                           "--seed", "1", "--seconds", "1"])
    assert rc == 1 and trace_reduce.load is load
