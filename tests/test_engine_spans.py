"""DecodeEngine's profiled spans on the profiler's own host trace: one
``engine.step`` per decoding step, its phases (``engine.refill``,
``engine.dispatch``, ``engine.readback``, ``engine.bookkeep``) disjoint
and inside it, and each prefill inside its refill."""
import glob

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.models import lm
from repro.models.common import ModelConfig
from repro.serve.engine import DecodeEngine, Request

PHASES = ("engine.refill", "engine.dispatch", "engine.readback",
          "engine.bookkeep")


def _engine_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]


def _start(span):
    return span[1]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _tiny(dtype=jnp.float32):
    return ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                       head_dim=16, dtype=dtype, scan_layers=False,
                       remat=False)


def test_a_profiled_run_gives_the_step_span_tree(tmp_path):
    cfg = _tiny()
    eng = DecodeEngine(cfg, lm.init_lm(jax.random.PRNGKey(0), cfg),
                       max_batch=2, max_len=64)
    # two prompt lengths in the first refill: two prefill groups
    for rid, prompt in enumerate([[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]]):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=2))
    jax.profiler.start_trace(str(tmp_path))
    try:
        outs = [eng.step() for _ in range(5)]
    finally:
        jax.profiler.stop_trace()
    assert outs[-1] == {}                    # empty batch: no step span
    spans = _engine_spans(tmp_path)
    steps = sorted((s for s in spans if s[0] == "engine.step"), key=_start)
    assert [(s[3]["active"], s[3]["seated"]) for s in steps] == \
        [(2, 2), (2, 0), (1, 1), (1, 0)]
    for step in steps:
        phases = sorted((s for s in spans
                         if s[0] in PHASES and _inside(s, step)), key=_start)
        names = [p[0] for p in phases]
        seated = step[3]["seated"]
        assert names == (["engine.refill"] if seated else []) + \
            ["engine.dispatch", "engine.readback", "engine.bookkeep"]
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    # every phase lies in exactly one step
    for p in (s for s in spans if s[0] in PHASES):
        assert sum(_inside(p, st) for st in steps) == 1
    refills = sorted((s for s in spans if s[0] == "engine.refill"),
                     key=_start)
    assert [r[3]["seated"] for r in refills] == [2, 1]
    prefills = [s for s in spans if s[0] == "engine.prefill"]
    assert sorted((p[3]["bucket"], p[3]["prompt_len"]) for p in prefills) \
        == [(1, 2), (1, 3), (1, 5)]
    for s in prefills:
        assert sum(_inside(s, r) for r in refills) == 1
    assert {s[0] for s in spans} == set(PHASES) | {"engine.step",
                                                   "engine.prefill"}


def test_the_weight_cast_span_counts_what_it_writes(tmp_path):
    """One ``engine.cast_weights`` per engine: a bfloat16 config casts the
    embedding, the head and the 7 matrices of each of its 2 layers, in
    bytes of bfloat16; weights already in compute dtype write nothing."""
    bf16 = _tiny(jnp.bfloat16)
    params = lm.init_lm(jax.random.PRNGKey(0), bf16)
    jax.profiler.start_trace(str(tmp_path))
    try:
        DecodeEngine(bf16, params, max_batch=2, max_len=16)
        DecodeEngine(bf16, lm.compute_params(params, bf16), max_batch=2,
                     max_len=16)
        DecodeEngine(_tiny(), params, max_batch=2, max_len=16)
    finally:
        jax.profiler.stop_trace()
    casts = sorted((s for s in _engine_spans(tmp_path)
                    if s[0] == "engine.cast_weights"), key=_start)
    # embed and lm_head, then per layer wq, wo, wk, wv and the three FFN
    elems = 2 * 128 * 64 + 2 * (2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128)
    assert [(s[3]["leaves"], s[3]["bytes"]) for s in casts] == \
        [(16, 2 * elems), (0, 0), (0, 0)]
