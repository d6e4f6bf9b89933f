"""Tests for the repro.obs observability layer (DESIGN.md SS.8): tracer
span semantics and Chrome trace-event schema, metrics-registry bucket
boundaries and labeling, disabled-mode zero-cost contract, flight
recorder trigger/rotation, and the instrumented fleet end-to-end."""
import json
import threading

import pytest

from repro import obs
from repro.obs import (NULL_SPAN, FlightRecorder, MetricsRegistry, Tracer,
                       summarize_events)
from repro.obs.metrics import WAIT_SLICE_BUCKETS


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Obs state is process-global on purpose; isolate every test."""
    obs.reset()
    yield
    obs.reset()


# -- tracer ------------------------------------------------------------------


def test_span_records_complete_event_with_args():
    tr = Tracer()
    with tr.span("work", cat="test", tid=7, k=1) as sp:
        sp.set("extra", "v")
    (ev,) = tr.events()
    assert ev["name"] == "work" and ev["ph"] == "X"
    assert ev["cat"] == "test" and ev["tid"] == 7
    assert ev["args"] == {"k": 1, "extra": "v"}
    assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0


def test_span_nesting_inner_contained_in_outer():
    tr = Tracer()
    with tr.span("outer", tid=1):
        with tr.span("inner", tid=1):
            pass
    inner, outer = tr.events()       # inner exits (and records) first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    # Perfetto nests slices by ts/dur containment on the same track
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_complete_is_posthoc_and_ordering_preserved():
    tr = Tracer()
    t0 = obs.now_ns()
    t1 = obs.now_ns()
    tr.complete("a", t0, t1, tid=3)
    tr.instant("marker", tid=3)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["a", "marker"]
    assert evs[0]["ph"] == "X" and evs[1]["ph"] == "i"
    assert evs[1]["s"] == "t"        # thread-scoped instant
    assert evs[1]["ts"] >= evs[0]["ts"]


def test_chrome_schema_valid_and_json_serializable():
    tr = Tracer()
    tr.name_track(0, "engine-0")
    with tr.span("s", tid=0):
        pass
    tr.instant("i", tid=0)
    doc = json.loads(json.dumps(tr.to_chrome()))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "thread_name"
    assert meta[0]["args"]["name"] == "engine-0"
    for ev in evs:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], float) and isinstance(
                ev["dur"], float)
            assert ev["dur"] >= 0.0


def test_tracer_export_and_summarize(tmp_path):
    tr = Tracer()
    for _ in range(3):
        with tr.span("hot"):
            pass
    with tr.span("cold"):
        pass
    path = tr.export(tmp_path / "sub" / "trace.json")
    doc = json.loads(path.read_text())
    rows = summarize_events(doc["traceEvents"])
    by_name = {r["name"]: r for r in rows}
    assert by_name["hot"]["count"] == 3 and by_name["cold"]["count"] == 1
    assert all(r["mean_us"] == pytest.approx(r["total_us"] / r["count"])
               for r in rows)


def test_tracer_thread_safety():
    tr = Tracer()

    def work():
        for _ in range(200):
            tr.complete("t", obs.now_ns(), obs.now_ns())

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == 800


# -- metrics registry --------------------------------------------------------


def test_histogram_bucket_boundaries_are_upper_bounds():
    reg = MetricsRegistry()
    # bounds (0,1,2,4,...): a value equal to a bound lands IN that bucket
    for v in (0.0, 1.0, 1.5, 4.0, 100.0):
        reg.observe("w", v, buckets=WAIT_SLICE_BUCKETS)
    h = reg.histogram("w")
    assert h.buckets == WAIT_SLICE_BUCKETS
    assert h.counts[0] == 1          # 0.0 <= 0
    assert h.counts[1] == 1          # 1.0 <= 1
    assert h.counts[2] == 1          # 1.5 <= 2
    assert h.counts[3] == 1          # 4.0 <= 4
    assert h.counts[-1] == 1         # 100.0 -> +inf overflow slot
    assert h.count == 5 and h.min == 0.0 and h.max == 100.0
    assert sum(h.counts) == h.count


def test_histogram_first_buckets_win_and_empty_requires_bounds():
    reg = MetricsRegistry()
    reg.observe("x", 1.0, buckets=(1.0, 2.0))
    reg.observe("x", 1.0, buckets=(9.0,))    # later bounds ignored
    assert reg.histogram("x").buckets == (1.0, 2.0)
    with pytest.raises(ValueError):
        obs.Histogram(())


def test_labeled_counters_are_distinct_and_formatted():
    reg = MetricsRegistry()
    reg.counter("admit", reason="ok", cls="default")
    reg.counter("admit", 2, reason="full", cls="default")
    reg.gauge("depth", 3.5, wid="0")
    assert reg.value("admit", reason="ok", cls="default") == 1
    assert reg.value("admit", reason="full", cls="default") == 2
    assert reg.value("admit") == 0            # unlabeled is a separate key
    snap = reg.as_dict()
    assert snap["counters"]["admit{cls=default,reason=full}"] == 2
    assert snap["gauges"]["depth{wid=0}"] == 3.5
    assert json.loads(json.dumps(snap)) == snap


# -- disabled-mode contract --------------------------------------------------


def test_disabled_mode_is_noop():
    assert not obs.enabled()
    assert obs.span("s") is NULL_SPAN         # shared singleton, no alloc
    assert obs.span("t", k=1) is obs.span("u")
    with obs.span("s") as sp:
        sp.set("k", "v")                      # chainable no-op
    obs.complete("c", obs.now_ns())
    obs.instant("i")
    obs.counter("n")
    obs.gauge("g", 1.0)
    obs.observe("h", 2.0)
    assert len(obs.tracer()) == 0
    snap = obs.metrics().as_dict()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


def test_enable_disable_roundtrip():
    obs.enable()
    assert obs.enabled()
    obs.counter("n")
    with obs.span("s"):
        pass
    assert obs.metrics().value("n") == 1 and len(obs.tracer()) == 1
    obs.disable()
    obs.counter("n")
    assert obs.metrics().value("n") == 1      # frozen while disabled
    obs.reset()
    assert len(obs.tracer()) == 0 and obs.flight_recorder() is None


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_ring_rotation():
    rec = FlightRecorder(capacity=4, miss_rate_threshold=None)
    for s in range(10):
        rec.record(s, {"depth": s})
    assert len(rec) == 4
    assert rec.slices() == [6, 7, 8, 9]       # oldest rotated out


def test_flight_recorder_triggers_once_per_episode(tmp_path):
    rec = FlightRecorder(capacity=8, miss_rate_threshold=0.5,
                         path=tmp_path / "flight.json")
    rec.record(0, {"depth": 1})
    assert rec.check(deadline_miss_rate=0.1) is None
    out = rec.check(deadline_miss_rate=0.9, context={"slice": 1})
    assert out is not None and out.exists()
    # still breaching: same episode, no second dump
    assert rec.check(deadline_miss_rate=0.95) is None
    assert rec.n_dumps == 1
    # recovery re-arms; next breach dumps to a numbered sibling file
    assert rec.check(deadline_miss_rate=0.0) is None
    out2 = rec.check(deadline_miss_rate=0.8)
    assert rec.n_dumps == 2
    assert out2.name == "flight.2.json" and out.exists() and out2.exists()
    payload = json.loads(out.read_text())
    assert payload["signals"]["deadline_miss_rate"] == 0.9
    assert payload["context"] == {"slice": 1}
    assert payload["frames"][0]["slice"] == 0


def test_flight_recorder_p99_trigger_and_in_memory_dump():
    rec = FlightRecorder(capacity=2, miss_rate_threshold=None,
                         p99_ms_threshold=5.0)
    rec.record(0, {})
    assert rec.check(p99_ms=1.0) is None
    assert rec.check(p99_ms=9.0) is None      # no path -> in-memory only
    assert rec.n_dumps == 1
    assert "p99_ms" in rec.last_dump["reason"]
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# -- instrumented fleet end-to-end ------------------------------------------


def test_instrumented_fleet_run_produces_spans_and_metrics(tmp_path):
    from repro import api
    from repro.fleet import make_trace, summarize

    rec = FlightRecorder(capacity=16, miss_rate_threshold=0.0)
    obs.enable(flight_recorder=rec)
    tr = make_trace("mmpp", n_slices=12, seed=0)
    fleet = api.fleet("tpu-pool", n_engines=2, forecaster="ewma")
    s = summarize(fleet.run(tr))
    assert s.n_completed > 0

    names = {e["name"] for e in obs.tracer().events()}
    assert {"fleet.slice", "worker.step", "sched.slice"} <= names
    snap = obs.metrics().as_dict()
    admits = {k: v for k, v in snap["counters"].items()
              if k.startswith("fleet.admission")}
    assert sum(admits.values()) == s.n_submitted
    wait = obs.metrics().histogram("fleet.queue_wait_slices",
                                   cls="default", tenant="-")
    assert wait is not None and wait.count == s.n_completed

    # frames recorded every slice; miss_rate_threshold=0 always fires once
    assert len(rec) > 0 and rec.n_dumps >= 1
    assert {"engines", "running", "lut_cache"} <= set(rec.last_dump
                                                      ["frames"][0])

    paths = obs.export(trace_path=tmp_path / "trace.json",
                       metrics_path=tmp_path / "metrics.json")
    doc = json.loads(paths["trace"].read_text())
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M"}
    assert {"engine-0", "engine-1"} <= tracks
    assert json.loads(paths["metrics"].read_text()) == snap


def test_api_obs_facade():
    from repro import api

    assert api.obs() is obs


# -- profiled spans (device path) ---------------------------------------------


def test_profiled_span_records_in_the_tracer_only_when_enabled():
    with obs.profiled_span("engine.step", active=3, seated=1):
        pass
    assert len(obs.tracer()) == 0
    obs.enable()
    with obs.profiled_span("engine.step", active=3, seated=1):
        with obs.profiled_span("engine.dispatch"):
            pass
    outer, inner = sorted(obs.tracer().events(), key=lambda e: e["ts"])
    assert (outer["name"], outer["cat"], outer["args"]) == \
        ("engine.step", "engine", {"active": 3, "seated": 1})
    assert inner["name"] == "engine.dispatch" and inner["args"] == {}
    assert outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_profiled_span_lands_on_the_profilers_host_plane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.profiled_span("engine.migration", n_weights=2,
                               placement="hp:3 lp:1"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [dict(e.stats)
             for plane in ProfileData.from_file(path[0]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name == "engine.migration"]
    assert found == [{"n_weights": 2, "placement": "hp:3 lp:1"}]
    assert len(obs.tracer()) == 0        # tracing stayed off
