"""The controls of a served cell: the reference computed in int8 and in
fp8, each put in the program's place on the tokens the program served,
beside the program's own readings.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

Each seed is one run of the cell (set-up, a window of ``--seconds`` at
the cell's own load, then the comparison), all in one process. Each
control is judged by the run's own comparison against the cell's limits
(``cells/<cell>.json``) and has to come out not correct. TPU only, like
``run.py``.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

QUANTS = ("int8", "fp8")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import jax

    import harness
    from repro.launch import compile_cache
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(cell, seed, args.seconds, False,
                             time.perf_counter(), controls=QUANTS)
        row = {"seed": seed, "correct": r["correct"],
               "served_tokens": r["notes"]["served_tokens_checked"],
               "program": {k: v["value"] for k, v in r["checks"].items()}}
        for q, c in r["notes"]["controls"].items():
            row[q] = {"correct": c["correct"],
                      **{k: v["value"] for k, v in c["checks"].items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
