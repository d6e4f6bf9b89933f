"""One run of one benchmark cell: set-up, the measured window, the
metrics and the comparison that decides ``correct``.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<config>.json``, its traffic in
``traffic/<mix>.json``, its limits in ``cells/<cell>.json``, each metric's
reader in ``metrics/<metric>.py`` and the device's peaks in
``peaks.json``. The program supplies only the system under test,
``DecodeEngine``, driven through ``submit`` and ``step``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import model
import reference
import traffic

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 10.0      # a traced run traces the last 10 s of its window


# -- what a cell is ----------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    conf: dict              # configs/<config>.json
    mix: dict               # traffic/<mix>.json
    limits: dict            # cells/<cell>.json "limits"
    end_to_end: List[dict]  # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    with open(os.path.join(BENCH, "cells", f"{name}.json")) as f:
        limits = json.load(f)["limits"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), model.load_config(w["config"]),
                traffic.load_mix(w["traffic"]), limits, e2e, per_layer)


def load_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(peaks)})")
    return peaks[kind]


def read_metric(name: str, run) -> Optional[float]:
    """The reader in ``metrics/<name>.py``; ``None`` when it finds
    nothing to read."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    v = mod.read(run)
    return None if v is None else float(v)


# -- what a run records ------------------------------------------------------

@dataclass
class Rec:
    """One request: the time it was sent, the program's request, and the
    host time at which each of its tokens came back."""
    due: float
    req: object
    times: List[float] = field(default_factory=list)


@dataclass
class Run:
    """Everything the metric readers may read."""
    cell: Cell
    dims: dict
    peak: Optional[dict] = None
    t0: float = 0.0
    t_end: float = 0.0
    setup_s: float = 0.0
    recs: List[Rec] = field(default_factory=list)
    # (t_before, t_after, tokens, summed context positions)
    steps: List[tuple] = field(default_factory=list)
    trace: Optional[dict] = None
    decode_module: Optional[str] = None
    window_compiles: int = 0
    served_tokens: int = 0

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 < t <= self.t_end


class Compiles:
    """Counts the programs compiled (or read from the persistent cache)
    while ``active``."""

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == BACKEND_COMPILE:
            self.n += 1


class Profiler:
    """Traces the last ``seconds`` of the window, when asked to."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = None
        self.mark = None

    def maybe_start(self, now: float, t_end: float) -> None:
        if self.seconds and self.dir is None \
                and now >= t_end - self.seconds:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self.mark = jax.profiler.TraceAnnotation("bench.traced")
            self.mark.__enter__()

    def stop(self) -> Optional[dict]:
        if self.dir is None:
            return None
        import glob

        import jax
        import trace_reduce
        self.mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            return trace_reduce.reduce(trace_reduce.load(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _module_name(fn) -> str:
    """The name the compiler gives a jitted function's module."""
    import re
    name = f"jit({getattr(fn, '__name__', 'fn')})"
    return re.sub(r"[^\w.-]", "_", name).rstrip("_")


# -- set-up ------------------------------------------------------------------

def prepare(cell: Cell, seed: int):
    """The program's config (checked against the file) and the seeded
    weights, made on the device in one program."""
    cfg = model.program_config(cell.conf)
    d = model.dims(cell.conf)
    params = model.make_weights(d, seed)
    model.check_tree(params, cfg)
    return cfg, d, params


def _warm_engine(eng, cfg, mix) -> None:
    """Compile every shape the mix can send, and no other: the prefill
    program of each prompt length at each power-of-two refill bucket
    (compiled ahead, not run), one request through the served path
    (prefill, state scatter, decode step), then the state scatter for
    every refill count, which the count shapes, fed a blank prefill
    state of the count's bucket."""
    import jax.numpy as jnp

    from repro.models import lm
    from repro.serve.engine import Request
    max_batch, max_len = eng.max_batch, eng.max_len
    lengths = sorted(set(traffic.length_block(mix["prompt_len"],
                                              mix["block"]).tolist()))
    buckets = sorted({eng._bucket(n) for n in range(1, max_batch + 1)})
    for L in lengths:
        for b in buckets:
            eng._prefill_fn(b, L).lower(
                eng.params, jnp.zeros((b, L), jnp.int32)).compile()
    eng.submit(Request(rid=-1, prompt=[1] * lengths[0], max_new_tokens=2))
    eng.run_until_done()
    for n in range(1, max_batch + 1):
        blank = lm.init_decode_state(cfg, eng._bucket(n), max_len)
        eng._scatter_state(list(range(n)), blank)
    eng.drain_completed()


# -- the served loop ----------------------------------------------------------

def serve(run: Run, cfg, params, seed: int, seconds: float,
          prof: Profiler, compiles: Compiles, t_proc: float):
    """The closed loop through ``DecodeEngine``; returns the engine."""
    from repro.serve.engine import DecodeEngine, Request
    mix = run.cell.mix
    eng = DecodeEngine(cfg, params, max_batch=mix["engine"]["max_batch"],
                       max_len=mix["engine"]["max_len"])
    _warm_engine(eng, cfg, mix)
    run.decode_module = _module_name(eng._step_fn)
    pending = deque(traffic.requests(mix, run.dims["vocab"], seed,
                                     int(mix["max_requests"])))
    by_rid: Dict[int, Rec] = {}

    def submit(due):
        r = pending.popleft()
        req = Request(rid=r.idx, prompt=r.prompt,
                      max_new_tokens=r.max_new_tokens)
        with _span("bench.submit"):
            eng.submit(req)
        rec = Rec(due, req)
        by_rid[r.idx] = rec
        run.recs.append(rec)

    draining = False

    def one_step():
        with _span("bench.step"):
            ts = time.perf_counter()
            out = eng.step()
            te = time.perf_counter()
        ctx = 0
        for rid in out:
            rec = by_rid[rid]
            rec.times.append(te)
            ctx += len(rec.req.prompt) + len(rec.req.out) - 1
            if rec.req.done and not draining:
                if not pending:
                    raise RuntimeError(f"the window outlasted the mix's "
                                       f"{mix['max_requests']} requests")
                submit(te)
        run.steps.append((ts, te, len(out), ctx))

    compiles.active = True
    run.t0 = time.perf_counter()
    run.setup_s = run.t0 - t_proc
    run.t_end = run.t0 + seconds
    for _ in range(int(mix["clients"])):
        submit(run.t0)
    while True:
        now = time.perf_counter()
        if now >= run.t_end:
            break
        prof.maybe_start(now, run.t_end)
        one_step()
    run.trace = prof.stop()
    compiles.active = False
    run.window_compiles = compiles.n
    # every request sent in the window gets its first token, however
    # late; the ones that never do are counted as failed
    draining = True
    t_stop = time.perf_counter() + 60.0
    while any(not r.times for r in run.recs) \
            and time.perf_counter() < t_stop:
        one_step()
    return eng


# -- correct -----------------------------------------------------------------

def _pick(recs: List[Rec], seed: int, k: int) -> List[Rec]:
    """The longest finished request and up to ``k - 1`` others drawn
    from the seed."""
    done = [r for r in recs if r.req.done]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.out))
    rest = [r for r in done if r is not longest]
    rng = traffic.rng_for(seed, 3)
    idx = rng.permutation(len(rest))[: k - 1]
    return [longest] + [rest[i] for i in sorted(idx)]


def _gap_readings(g: np.ndarray) -> Dict[str, float]:
    if not g.size:
        return {"max_gap": float("inf"), "mean_gap": float("inf")}
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean())}


def judge(readings: Dict[str, float], limits: dict) -> Dict[str, dict]:
    """Each number beside its limit."""
    return {k: {"value": v, "limit": limits[k]} for k, v in readings.items()}


def passes(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def check_served(run: Run, params, seed: int, controls=()):
    """Finished requests of the window against the reference: the widest
    and the mean gap of the served tokens over a sample of them drawn
    from the seed, and the finished requests of the wrong length or with
    an id outside the vocabulary. For each of ``controls`` (``"int8"``,
    ``"fp8"``), also that control's gaps at the same positions, judged
    against the same limits."""
    chk = run.cell.mix["check"]
    lim = dict(run.cell.limits, wrong_answers=0)
    picked = _pick(run.recs, seed, int(chk["sequences"]))
    vocab = run.dims["vocab"]
    wrong = sum(len(r.req.out) != r.req.max_new_tokens
                or not all(0 <= t < vocab for t in r.req.out)
                for r in run.recs if r.req.done)
    seqs = [(r.req.prompt, r.req.out) for r in picked]
    run.served_tokens = sum(len(s[1]) for s in seqs)

    def read(quant):
        return _gap_readings(reference.gaps(
            params, run.dims, seqs, pad_to=chk["pad_to"], rows=chk["rows"],
            quant=quant))

    checks = judge(dict(read(None), wrong_answers=wrong), lim)
    return checks, {q: judge(read(q), lim) for q in controls}


# -- one run -----------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_proc: float, controls=()) -> dict:
    """Set up, measure, check; returns the result line's object.
    ``controls`` adds each control's reading and verdict under ``notes``
    (``control.py``; the benchmark's own runs never ask for one)."""
    import jax
    compiles = Compiles()
    dev = jax.devices()[0]
    cfg, d, params = prepare(cell, seed)
    run = Run(cell, d)
    if dev.platform == "tpu":
        run.peak = load_peaks(dev.device_kind)
    prof = Profiler(min(TRACE_SECONDS, seconds) if trace else 0.0)
    eng = serve(run, cfg, params, seed, seconds, prof, compiles, t_proc)
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    # the program's state is freed before the reference runs
    del eng
    checks, ctl = check_served(run, params, seed, controls)
    due = [r for r in run.recs if run.t0 <= r.due <= run.t_end]
    metrics = cell.per_layer if trace else cell.end_to_end
    values = {}
    for m in metrics:
        v = read_metric(m["name"], run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": passes(checks), "attempted": len(due),
              "failed": sum(not r.times for r in due), "metrics": values,
              "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    steps = [te - ts for ts, te, _, _ in run.steps if run.in_window(te)]
    result["notes"] = {
        "window_compiles": run.window_compiles,
        "decode_module": run.decode_module,
        "served_tokens_checked": run.served_tokens,
        # how the window's time went: its steps, their median (s), and
        # the three longest (s, and s into the window)
        "steps": len(steps),
        "step_median_s": float(np.median(steps)) if steps else None,
        "slowest_steps": sorted(((te - ts, ts - run.t0)
                                 for ts, te, _, _ in run.steps
                                 if run.in_window(te)), reverse=True)[:3],
        "controls": {q: {"correct": passes(c), "checks": c}
                     for q, c in ctl.items()},
    }
    result["checks"] = checks
    return result
