"""One-chip smoke run of the served path at the published widths.

    python chip_smoke.py                      # on a machine with one TPU

Builds ``internlm2_1_8b`` at its published config (24 layers, d_model
2048, 16 query / 8 KV heads, d_ff 8192, vocab 92,544) with seeded random
weights and drives, in one process, the entry points a user calls:

  a. serve with placement: ``api.engine("tpu-pool", ...)`` runs time
     slices of a ``workloads.SCENARIOS`` load trace, re-tiering the
     weights when the placement changes;
  b. answer requests: ``DecodeEngine`` serves prompts of different
     lengths to completion; its first-step logits for one prompt are
     compared with ``lm.forward`` on the same prompt (max |diff| at most
     ``LOGIT_RTOL`` times max |forward logit|: both run in bfloat16);
  c. build placement LUTs with the fused ``lut_pipeline`` kernel
     (``api.lut(..., solver="dp")`` on ``tpu-pool`` and ``cxl-tier-3``),
     which must report the ``pallas`` backend and equal the ``ref``
     backend's build entry for entry;
  d. run the ``pim_mac`` kernel on a full-width FFN weight through the
     engine's tiered matmul, checked against ``pim_matmul_ref``.

Each phase is a function of the config, so a test can rehearse them at
the smoke size on the CPU with the kernels in interpret mode. ``main``
refuses any platform but ``tpu``, and refuses to run while
``REPRO_LUT_BACKEND`` or ``REPRO_KNAPSACK_BACKEND`` would override the
kernel choice. Wall times printed here are of the first run in a fresh
process, compilation included (or read from a warm persistent compile
cache), not benchmark numbers. The last line of
standard output is a JSON object naming the device; it is printed only
when every phase and every comparison passed.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api, obs  # noqa: E402
from repro.configs import describe, get_config  # noqa: E402
from repro.core import workloads  # noqa: E402
from repro.core.solvers import LUTMethodSolver  # noqa: E402
from repro.kernels.pim_mac.ops import pim_matmul  # noqa: E402
from repro.kernels.pim_mac.ref import pim_matmul_ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.quant.int8 import quantize_activations  # noqa: E402
from repro.serve.engine import DecodeEngine, Request  # noqa: E402

ARCH = "internlm2_1_8b"
BACKEND_ENVS = ("REPRO_LUT_BACKEND", "REPRO_KNAPSACK_BACKEND")
LOGIT_RTOL = 0.05


class PhaseError(AssertionError):
    """A phase's output failed its check."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


def init_params(cfg, seed: int = 0):
    """Seeded random weights, built on the device in one program."""
    params = jax.jit(lm.init_lm, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    return jax.block_until_ready(params)


def phase_serve(cfg, params, *, scenario: str = "case6_random",
                n_slices: int = 10):
    """(a) Time slices through the tpu-pool placement engine; at least
    one slice after the first must re-tier the weights."""
    eng = api.engine("tpu-pool", cfg, params)
    loads = workloads.SCENARIOS[scenario][:n_slices]
    print(f"  time slice {eng.t_slice_ms} ms; loads {loads}")
    for i, n in enumerate(loads):
        r = eng.run_slice(n)
        used = {k: v for k, v in r.report.placement.items() if v}
        print(f"  slice {i} load {n} retiered={r.retiered} "
              f"tokens={len(r.tokens)} placement={used}")
    retiers = sum(r.retiered for r in eng.history[1:])
    _check(len(eng.history) >= 6, f"only {len(eng.history)} slices ran")
    _check(retiers >= 1, "no slice after the first re-tiered the weights")
    _check(all(len(r.tokens) == min(r.report.n_done, eng.max_batch)
               for r in eng.history), "a slice decoded the wrong count")
    print(f"  {len(eng.history)} slices, {retiers} re-tiers after the "
          f"first")
    return eng


def phase_requests(cfg, params, *, prompt_lens=(3, 5, 8, 13),
                   max_new_tokens: int = 4, seed: int = 0):
    """(b) DecodeEngine answers one request per prompt length; the first
    step's logits of request 0 must match ``lm.forward``."""
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, L)]
               for L in prompt_lens]
    eng = DecodeEngine(cfg, params, max_batch=len(prompts), max_len=64)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p,
                           max_new_tokens=max_new_tokens))
    eng.step()                    # seats every request: slot = rid
    first = np.asarray(eng.last_logits[0], np.float32)
    eng.run_until_done()
    done = sorted(eng.completed, key=lambda r: r.rid)
    _check(len(done) == len(prompts),
           f"{len(done)} of {len(prompts)} requests completed")
    for r in done:
        _check(len(r.out) == max_new_tokens
               and all(0 <= t < cfg.vocab_size for t in r.out),
               f"request {r.rid} answered {r.out}")
        print(f"  request {r.rid}: prompt {len(r.prompt)} tokens -> "
              f"{r.out}")
    fwd = jax.jit(lambda p, t: lm.forward(p, cfg, t)[0])
    ref = np.asarray(fwd(params, jnp.asarray([prompts[0]], jnp.int32))
                     [0, -1], np.float32)
    _check(bool(np.isfinite(first).all()), "non-finite decode logits")
    err = float(np.max(np.abs(first - ref)))
    scale = float(np.max(np.abs(ref)))
    print(f"  first-step logits vs lm.forward: max|diff| {err} "
          f"(max|logit| {scale}, bound {LOGIT_RTOL} x max|logit|); "
          f"argmax {int(first.argmax())} vs {int(ref.argmax())}")
    _check(err <= LOGIT_RTOL * scale, "decode logits drifted from forward")
    return done


def phase_lut(cfg, *, expect: str = "pallas",
              substrates=("tpu-pool", "cxl-tier-3")):
    """(c) Fused-kernel dp LUT builds equal the ref backend's, entry for
    entry; the kernel must be the backend that ran."""
    ref_solver = LUTMethodSolver("dp", "dp", lut_backend="ref")
    for name in substrates:
        lut = api.lut(name, cfg, solver="dp")
        ref = api.lut(name, cfg, solver=ref_solver)
        feasible = sum(e.feasible for e in lut.entries)
        print(f"  {name}: backend={lut.backend} entries={len(lut.entries)} "
              f"feasible={feasible} equal_to_ref={lut.entries == ref.entries}")
        _check(lut.backend == expect,
               f"{name}: LUT built on {lut.backend}, expected {expect}")
        _check(ref.backend == "ref", f"{name}: reference built on "
                                     f"{ref.backend}")
        _check(lut.entries == ref.entries,
               f"{name}: {expect} LUT differs from the ref build")
        _check(feasible > 0, f"{name}: no feasible LUT entry")


def phase_pim_mac(eng, *, backend: str = "auto", expect: str = "pallas",
                  rows: int = 8, seed: int = 0):
    """(d) One tiered FFN matmul with an int8 tier through the pim_mac
    kernel, against the jnp reference. ``backend`` is what the caller
    asks for, ``expect`` the backend that must have run."""
    K = eng.model_spec.n_params
    eng.apply_placement({"hp_sram": K // 2, "hp_mram": K - K // 2})
    key = next(iter(eng._tiered))
    segs = eng._tiered[key]
    int8 = [n for n, s in segs.items() if "q" in s]
    _check(bool(int8), f"placement left no int8 tier in {sorted(segs)}")
    seg = segs[int8[0]]
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (rows, seg["q"].shape[0]), jnp.float32)
    was_on = obs.enabled()
    obs.enable()
    try:
        before = obs.metrics().value("kernels.pim_mac.dispatch",
                                     backend=expect)
        y = np.asarray(eng.tiered_forward(x, backend=backend))
        xq, sx = quantize_activations(x)
        mac = np.asarray(pim_matmul(xq, seg["q"], sx, seg["scale"],
                                    backend=backend))
        ran = obs.metrics().value("kernels.pim_mac.dispatch",
                                  backend=expect) - before
    finally:
        if not was_on:
            obs.disable()
    y_ref = np.asarray(eng.tiered_forward(x, backend="ref"))
    mac_ref = np.asarray(pim_matmul_ref(xq, seg["q"], sx, seg["scale"]))
    w_shape = tuple(seg["q"].shape)
    err = float(np.max(np.abs(mac - mac_ref)))
    print(f"  {key} tier {int8[0]} {w_shape} int8: {ran} {expect} "
          f"dispatches; pim_matmul vs pim_matmul_ref max|diff| {err}; "
          f"tiered_forward {tuple(y.shape)} vs ref max|diff| "
          f"{float(np.max(np.abs(y - y_ref)))}")
    _check(ran >= 2, f"pim_mac ran {ran} times on {expect}")
    scale = float(np.max(np.abs(mac_ref)))
    _check(err <= 1e-6 * scale, "pim_mac result differs from the reference")
    _check(np.allclose(y, y_ref, rtol=0, atol=1e-6 * float(
        np.max(np.abs(y_ref)))), "tiered matmul differs from the reference")


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def run_phases(cfg, *, pim_backend: str = "auto",
               expect_backend: str = "pallas"):
    """Every phase in order; returns the wall time of each (seconds).
    ``expect_backend`` is the kernel backend phases c and d must report;
    ``pim_backend`` is the one phase d asks ``pim_matmul`` for."""
    times = {}

    def timed(name, fn, *a, **kw):
        print(f"phase {name}")
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        times[name] = time.perf_counter() - t0
        print(f"phase {name}: ok, {times[name]} s wall (first run in this "
              f"process, compile or cache read included; not a benchmark "
              f"number)")
        return out

    params = timed("init", init_params, cfg)
    eng = timed("a_serve", phase_serve, cfg, params)
    timed("b_requests", phase_requests, cfg, params)
    timed("c_lut", phase_lut, cfg, expect=expect_backend)
    timed("d_pim_mac", phase_pim_mac, eng, backend=pim_backend,
          expect=expect_backend)
    return times


def main() -> int:
    forced = [v for v in BACKEND_ENVS if os.environ.get(v)]
    if forced:
        print(f"chip_smoke: refusing to run with {', '.join(forced)} set: "
              f"the kernels must be chosen by the platform",
              file=sys.stderr)
        return 2
    import repro
    where = [os.path.abspath(p) for p in repro.__path__]
    if not all(p.startswith(ROOT + os.sep) for p in where):
        print(f"chip_smoke: repro imported from {where}, not from this "
              f"checkout", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    print(f"compile cache: {compile_cache.enable()}")
    cfg = get_config(ARCH)
    print(f"device_kind: {dev.device_kind} (count {len(jax.devices())})")
    print(f"config: {ARCH} {describe(cfg)} dtype={jnp.dtype(cfg.dtype).name}")
    try:
        times = run_phases(cfg)
    except Exception as e:            # any failed phase fails the run
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        print(f"peak_bytes_in_use: {_peak_bytes(dev)}")
        return 1
    print(f"peak_bytes_in_use: {_peak_bytes(dev)}")
    print("phase wall times (s, first run in this process): " + ", ".join(
        f"{k}={v}" for k, v in times.items()))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
