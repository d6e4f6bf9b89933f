"""Reduce a profiler trace (``.xplane.pb``) to device time.

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
  inside the traced window, averaged over the devices;
* per program: device time and launches of each compiled module (the
  ``XLA Modules`` line), keyed by module name without its hash;
* per operation: device time summed by HLO instruction name;
* idle gaps: each stretch of the window in which the device ran
  nothing, named by the host span (``bench.*`` annotations on the host
  plane) that overlaps it most, or ``other``.

Device and host clocks are aligned by the program launches both record
(``run_id``): a launch's completion reaches the host after the device
ends it, so the least of host completion minus device end is the
offset.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]


def _stats(ev) -> dict:
    return dict(ev.stats)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_base(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """Planes of the trace as plain lists: ``devices`` maps a device
    plane to its ops and modules, ``host`` holds ``bench.*`` spans and
    launch completions (all times in ns on the host clock)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[Tuple[str, float, float]] = []
    host_done: Dict[int, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [(op_base(e.name), e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [
                        (module_base(e.name), e.start_ns,
                         e.start_ns + e.duration_ns,
                         _stats(e).get("run_id"))
                        for e in line.events]
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == "CompleteCallbacks":
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            host_done.setdefault(int(rid), e.start_ns)
    offsets = sorted(host_done[int(r)] - end
                     for dev in devices.values()
                     for _, _, end, r in dev["modules"]
                     if r is not None and int(r) in host_done)
    shift = offsets[0] if offsets else 0.0
    for dev in devices.values():
        dev["ops"] = [(n, s + shift, e + shift) for n, s, e in dev["ops"]]
        dev["modules"] = [(n, s + shift, e + shift)
                          for n, s, e, _ in dev["modules"]]
    return {"devices": devices, "spans": spans, "shift_ns": shift}


def reduce(tr: dict, window: Optional[Interval] = None) -> dict:
    """Busy and idle time, per-module and per-op device time over
    ``window`` (ns, host clock; default: the ``bench.traced`` span)."""
    if window is None:
        marks = [(s, e) for n, s, e in tr["spans"] if n == "bench.traced"]
        if not marks:
            raise ValueError("trace holds no bench.traced span")
        window = marks[0]
    lo, hi = window
    n_dev = max(len(tr["devices"]), 1)
    busy = 0.0
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    host = [(n, s, e) for n, s, e in tr["spans"] if n != "bench.traced"]
    for dev in tr["devices"].values():
        u = clip(union([(s, e) for _, s, e in dev["ops"]]), lo, hi)
        busy += sum(e - s for s, e in u)
        for name, s, e in dev["modules"]:
            if s >= lo and e <= hi:
                modules[name][0] += e - s
                modules[name][1] += 1
        for name, s, e in dev["ops"]:
            if e > lo and s < hi:
                ops[name] += min(e, hi) - max(s, lo)
        edges = [lo] + [t for iv in u for t in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            best, who = 0.0, "other"
            for name, s, e in host:
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, who = ov, name
            gaps[who] += ge - gs
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n_dev * ns,
        "modules": {k: {"seconds": v[0] / n_dev * ns,
                        "launches": v[1] / n_dev}
                    for k, v in modules.items()},
        "device_ops": sorted(([k, v / n_dev * ns] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v / n_dev * ns] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:10],
    }
