"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Two modes:
  * ``--engine batch``  - plain batched decode engine (slot continuous
    batching).
  * ``--engine hetero`` - the HH-PIM heterogeneous runtime: requests flow
    through time slices, weight placement re-solved per slice across
    {hp,lp} x {bf16,int8} tiers (the paper's technique, TPU constants).
    Built through the ``repro.api`` facade; ``--substrate`` / ``--solver``
    pick registry entries (DESIGN.md SS.5).

Both run the reduced (smoke) config of ``--arch`` unless
``--full-config`` selects the published one; the banner prints the
dimensions that ran. Seeded random weights either way.
"""
from __future__ import annotations

import argparse

import jax

from repro import api
from repro.configs import (ARCH_IDS, canonical, describe, get_config,
                           get_smoke_config)
from repro.core import workloads
from repro.launch import compile_cache
from repro.models import lm
from repro.serve.engine import DecodeEngine, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b",
                    help=f"one of {ARCH_IDS}")
    ap.add_argument("--engine", choices=("batch", "hetero"),
                    default="hetero")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--scenario", default="case6_random")
    ap.add_argument("--substrate", default="tpu-pool",
                    help=f"one of {api.available_substrates()}")
    ap.add_argument("--solver", default=None,
                    help=f"placement solver, one of {sorted(api.SOLVERS)}")
    ap.add_argument("--full-config", action="store_true",
                    help="run the published config instead of the "
                         "reduced smoke config")
    args = ap.parse_args()

    compile_cache.enable()
    cfg = (get_config if args.full_config else get_smoke_config)(args.arch)
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    print(f"arch={canonical(args.arch)} ({describe(cfg)}, "
          f"{'published' if args.full_config else 'reduced'} config) "
          f"engine={args.engine}")

    if args.engine == "batch":
        eng = DecodeEngine(cfg, params, max_batch=4, max_len=64)
        for r in range(args.requests):
            eng.submit(Request(rid=r, prompt=[1 + r, 2, 3],
                               max_new_tokens=args.max_new_tokens))
        done = eng.run_until_done()
        for req in done:
            print(f"  request {req.rid}: {len(req.out)} tokens "
                  f"{req.out[:8]}")
        return

    over = {"solver": args.solver} if args.solver else {}
    try:
        eng = api.engine(args.substrate, cfg, params, max_batch=4, **over)
    except ValueError as e:
        raise SystemExit(str(e))
    loads = workloads.SCENARIOS[args.scenario][:10]
    print(f"time slice {eng.t_slice_ms:.3f} ms; loads {loads}")
    for i, n in enumerate(loads):
        r = eng.run_slice(min(n, eng.max_batch))
        used = {k: v for k, v in r.report.placement.items() if v}
        print(f"  slice {i:2d} load {n:2d} E={r.report.energy_pj*1e-6:9.2f}"
              f" uJ retier={'y' if r.retiered else 'n'} "
              f"{'ok' if r.report.deadline_met else 'MISS'} {used}")
    print(f"total {eng.energy_uj():.1f} uJ, "
          f"{eng.deadline_misses()} deadline misses")


if __name__ == "__main__":
    main()
