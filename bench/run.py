"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (the last seconds of the window under the profiler).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), with every number compared beside its limit under
``checks``, last; the same numbers close standard error. The run
refuses any platform but ``tpu`` and fewer chips than the cell asks for,
and then prints no result.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [BENCH, src]
    import jax

    import harness
    from repro.launch import compile_cache
    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    print(f"compile cache: {compile_cache.enable()}")
    # every program, the engines' small eager ones too, goes to the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROC)
    notes = result.pop("notes")
    checks = result.pop("checks")
    print(f"compiles inside the window: {notes['window_compiles']}")
    print(f"decode program: {notes['decode_module']}")
    print(f"served tokens compared with the reference: "
          f"{notes['served_tokens_checked']}")
    print(f"steps in the window: {notes['steps']}, median "
          f"{notes['step_median_s']} s")
    print(f"slowest steps (s, s into the window): "
          f"{notes['slowest_steps']}")
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
