"""Compile each served cell's decode step and its largest prefill for a
described v5e chip, with no chip attached, and print what the compiler
says about memory.

    JAX_PLATFORMS=cpu python bench/aot_compile.py [cell ...]

It compiles the engine's own programs (``DecodeEngine``'s jitted
``lm.decode_step`` and its prefill scan) from shapes only: nothing is
allocated, and nothing runs.
"""
import os
import sys
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402
import model  # noqa: E402
import traffic  # noqa: E402


def _on(shard, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard),
        tree)


def compile_cell(name: str, shard) -> None:
    from repro.models import lm
    from repro.serve.engine import DecodeEngine
    cell = harness.load_cell(name)
    cfg = model.program_config(cell.conf)
    B, S = cell.mix["engine"]["max_batch"], cell.mix["engine"]["max_len"]
    params = _on(shard, jax.eval_shape(lambda k: lm.init_lm(k, cfg),
                                       jax.random.PRNGKey(0)))
    state = _on(shard, jax.eval_shape(
        lambda: lm.init_decode_state(cfg, B, S)))
    toks = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=shard)
    step = jax.jit(lambda p, st, tk, pos: lm.decode_step(p, cfg, st, tk,
                                                         pos))
    c = step.lower(params, state, toks, toks).compile()
    print(f"{name}: decode step batch {B} max_len {S}: "
          f"{c.memory_analysis()}")
    L = max(traffic.length_block(cell.mix["prompt_len"],
                                 cell.mix["block"]))
    eng = types.SimpleNamespace(cfg=cfg, max_len=S, _prefill_fns={})
    fn = DecodeEngine._prefill_fn(eng, B, int(L))
    prompts = jax.ShapeDtypeStruct((B, int(L)), jnp.int32, sharding=shard)
    c = fn.lower(params, prompts).compile()
    print(f"{name}: prefill bucket {B} length {L}: {c.memory_analysis()}")


def main(names) -> None:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    shard = SingleDeviceSharding(topo.devices[0])
    for name in names:
        compile_cell(name, shard)


if __name__ == "__main__":
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    main(sys.argv[1:] or cells)
