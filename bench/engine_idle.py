"""Split a traced run's device idle by the decode engine's own phases.

    python3 bench/engine_idle.py --workload <cell> --seed <n> \
        --seconds <s>

Runs the cell as ``run.py --trace 1`` does, with the same output and
result line, then prints two lines:

* ``idle by engine phase (s): {...}``: the traced window's device idle
  cut at the edges of ``DecodeEngine``'s spans (``engine.*`` annotations
  on the host plane, DESIGN.md §8), each piece under the phase that
  covers it (``engine.refill``, ``engine.dispatch``, ``engine.readback``,
  ``engine.bookkeep``), under ``engine.step`` where only a step covers
  it, else under ``outside``. The pieces sum to ``window_s - busy_s``.
* ``per decode launch (ms): {...}``: refill, read-back and host-loop
  idle (dispatch, bookkeeping and a step's own time) over the
  ``engine.dispatch`` spans in the window, and the prefill program's
  device time per prompt position its ``engine.prefill`` spans scanned.

``trace_reduce`` keeps only the harness's ``bench.*`` spans, so this tool
reads the engine's spans from the same trace file as it is loaded.
"""
from __future__ import annotations

import sys
from collections import Counter
from typing import Dict, List, Optional, Tuple

import trace_reduce
from trace_reduce import Interval

# the disjoint phases of DecodeEngine.step, each inside an engine.step
PHASES = ("engine.refill", "engine.dispatch", "engine.readback",
          "engine.bookkeep")
PREFILL_MODULE = "jit_prefill"

Span = Tuple[str, float, float, dict]


def engine_spans(path: str) -> List[Span]:
    """The ``engine.*`` spans of the trace's host plane, with their
    attrs (ns, host clock)."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Where two sorted lists of disjoint intervals overlap."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` less ``b``, both sorted lists of disjoint intervals."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def measure(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def split(tr: dict, spans: List[Span],
          window: Optional[Interval] = None) -> dict:
    """The device idle of ``window`` (default: the ``bench.traced``
    span) under each phase, in s; the count of each ``engine.*`` span
    inside the window; the prompt positions its prefills scanned; and
    the prefill program's device time there."""
    r = trace_reduce.reduce(tr, window)
    if window is None:
        window = next((s, e) for n, s, e in tr["spans"]
                      if n == "bench.traced")
    lo, hi = window
    covers = [(p, trace_reduce.union([(s, e) for n, s, e, _ in spans
                                      if n == p]))
              for p in PHASES + ("engine.step",)]
    idle: Dict[str, float] = dict.fromkeys(
        PHASES + ("engine.step", "outside"), 0.0)
    for dev in tr["devices"].values():
        u = trace_reduce.clip(
            trace_reduce.union([(s, e) for _, s, e in dev["ops"]]), lo, hi)
        edges = [lo] + [t for iv in u for t in iv] + [hi]
        # phases first, then a step's own time, then what no step covers
        rest = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for name, cover in covers:
            idle[name] += measure(intersect(rest, cover))
            rest = subtract(rest, cover)
        idle["outside"] += measure(rest)
    n_dev = max(len(tr["devices"]), 1)
    inside = [(n, a) for n, s, e, a in spans if s >= lo and e <= hi]
    prefill = r["modules"].get(PREFILL_MODULE, {})
    return {
        "window_s": r["window_s"], "busy_s": r["busy_s"],
        "idle_by_engine": {k: v / n_dev * 1e-9 for k, v in idle.items()},
        "engine_spans": dict(Counter(n for n, _ in inside)),
        # a prefill launch scans all but the last prompt token
        "prefill_positions": sum(a["prompt_len"] - 1 for n, a in inside
                                 if n == "engine.prefill"),
        "prefill_launches": prefill.get("launches", 0),
        "prefill_s": prefill.get("seconds", 0.0),
    }


def per_launch_ms(sp: dict) -> Dict[str, Optional[float]]:
    """Idle per decode launch, and prefill device time per prompt
    position; ``None`` where the window holds nothing to divide by, or
    the prefill launches and ``engine.prefill`` spans do not pair."""
    launches = sp["engine_spans"].get("engine.dispatch", 0)
    idle = sp["idle_by_engine"]

    def per(names):
        return sum(idle[n] for n in names) / launches * 1e3 \
            if launches else None

    paired = sp["prefill_positions"] and sp["prefill_launches"] == \
        sp["engine_spans"].get("engine.prefill", 0)
    return {
        "refill_idle_ms": per(("engine.refill",)),
        "readback_idle_ms": per(("engine.readback",)),
        "loop_idle_ms": per(("engine.dispatch", "engine.bookkeep",
                             "engine.step")),
        "prefill_ms_per_position": sp["prefill_s"] /
        sp["prefill_positions"] * 1e3 if paired else None,
    }


def main(argv=None) -> int:
    import run
    argv = list(sys.argv[1:] if argv is None else argv)
    found: List[dict] = []
    load = trace_reduce.load

    def load_and_split(path):
        tr = load(path)
        found.append(split(tr, engine_spans(path)))
        return tr

    trace_reduce.load = load_and_split
    try:
        rc = run.main(argv + ["--trace", "1"])
    finally:
        trace_reduce.load = load
    for sp in found:
        print(f"idle by engine phase (s): {sp['idle_by_engine']}")
        print(f"per decode launch (ms): {per_launch_ms(sp)}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
