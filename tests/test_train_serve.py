"""Integration tests: trainer loop (+ resume, compression), decode engine,
and the HH-PIM hetero serving runtime."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.data.synthetic import DataConfig
from repro.models import lm
from repro.models.common import ModelConfig
from repro.models.hetero_linear import split_weight, tiered_matmul
from repro.optim.adamw import OptimizerConfig
from repro.serve.engine import DecodeEngine, Request
from repro.serve.hetero import HeteroServeEngine, tpu_arch
from repro.train.trainer import Trainer, TrainerConfig


def _tiny_cfg():
    return ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                       head_dim=16, dtype=jnp.float32, scan_layers=False,
                       remat=False)


def _tiny_trainer(tmp_path=None, steps=30, compression=False, seed=0):
    cfg = _tiny_cfg()
    return Trainer(
        cfg,
        OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=steps,
                        weight_decay=0.0),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                   seed=seed),
        TrainerConfig(steps=steps, ckpt_every=10,
                      ckpt_dir=str(tmp_path) if tmp_path else None,
                      grad_compression=compression))


def test_trainer_loss_decreases(tmp_path):
    out = _tiny_trainer(tmp_path).run()
    assert out["final_loss"] < out["first_loss"] * 0.9
    assert out["steps"] == 30


def test_trainer_resume_continuity(tmp_path):
    t1 = _tiny_trainer(tmp_path, steps=20)
    t1.run()
    t1._ckpt.wait()
    # new process-equivalent: fresh trainer resumes from step 20 checkpoint
    t2 = _tiny_trainer(tmp_path, steps=25)
    assert t2.maybe_resume()
    assert t2.step == 20
    out = t2.run()
    assert out["steps"] == 25


def test_trainer_preemption_stop(tmp_path):
    t = _tiny_trainer(tmp_path, steps=1000)
    orig_step = t._jit_step

    def stepper(*a, **k):
        if t.step >= 5:
            t.request_stop()
        return orig_step(*a, **k)

    t._jit_step = stepper
    out = t.run()
    assert out["steps"] <= 7      # stopped promptly
    t._ckpt.wait()
    from repro.checkpoint import ckpt
    assert ckpt.latest_step(tmp_path) == out["steps"]


def test_trainer_with_compression_converges(tmp_path):
    base = _tiny_trainer(None, steps=30, seed=1).run()
    comp = _tiny_trainer(None, steps=30, compression=True, seed=1).run()
    assert comp["final_loss"] < comp["first_loss"] * 0.9
    # compressed path tracks the uncompressed one loosely
    assert comp["final_loss"] < base["final_loss"] * 1.5 + 0.5


# -- serving ------------------------------------------------------------------


def test_decode_engine_serves_batched_requests():
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(cfg, params, max_batch=4, max_len=64)
    for r in range(6):
        eng.submit(Request(rid=r, prompt=[1 + r, 2, 3], max_new_tokens=5))
    eng.run_until_done()
    done = [r for r in eng.slots if r is not None] + eng.queue
    assert all(len(r.out) == 5 for r in done if r.done)
    assert sum(r.done for r in done) >= 4


def test_run_until_done_keeps_refilled_slot_completions():
    """A finished request whose slot is refilled from the queue must still
    be returned (the seed dropped it)."""
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(cfg, params, max_batch=2, max_len=64)
    for r in range(6):
        eng.submit(Request(rid=r, prompt=[1 + r, 2, 3], max_new_tokens=3))
    done = eng.run_until_done()
    assert sorted(r.rid for r in done) == list(range(6))
    assert all(r.done and len(r.out) == 3 for r in done)


def test_run_until_done_returns_only_this_runs_completions():
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(cfg, params, max_batch=2, max_len=64)
    eng.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    first = eng.run_until_done()
    assert [r.rid for r in first] == [0]
    eng.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=2))
    second = eng.run_until_done()
    assert [r.rid for r in second] == [1]    # batch A not double-counted
    assert len(eng.drain_completed()) == 2   # accumulator holds both


def test_prefill_recurrent_and_local_state_uncontaminated():
    """Ragged refill waves must leave recurrent (rglru) state and
    local-attention ring buffers exactly as a token-by-token reference
    decode would - prompt grouping by exact length, no pad tokens."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("recurrentgemma_2b")   # rglru + local attention
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    prompt = [9, 4, 7]

    eng = DecodeEngine(cfg, params, max_batch=2, max_len=64)
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=1))
    eng.submit(Request(rid=1, prompt=[5, 6, 8, 2, 3], max_new_tokens=1))
    eng._fill_slots()                             # ragged wave: lengths 3, 5
    le, _ = lm.decode_step(params, cfg, eng._state, eng._toks,
                           jnp.asarray(eng._slot_pos))

    state = lm.init_decode_state(cfg, 1, 64)
    for t, tok in enumerate(prompt[:-1]):
        _, state = lm.decode_step(params, cfg, state,
                                  jnp.asarray([tok], jnp.int32),
                                  jnp.int32(t))
    lr, _ = lm.decode_step(params, cfg, state,
                           jnp.asarray([prompt[-1]], jnp.int32),
                           jnp.int32(len(prompt) - 1))
    np.testing.assert_allclose(np.asarray(le)[0], np.asarray(lr)[0],
                               atol=1e-4)


def test_drain_completed_clears_and_accumulates():
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(cfg, params, max_batch=4, max_len=64)
    for r in range(3):
        eng.submit(Request(rid=r, prompt=[1, 2], max_new_tokens=2))
    eng.run_until_done()
    drained = eng.drain_completed()
    assert len(drained) == 3
    assert eng.drain_completed() == []


def test_batched_prefill_handles_ragged_prompts():
    """Slot refill feeds prompts through one jitted prefill call; ragged
    prompt lengths in the same wave must still decode to completion."""
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(cfg, params, max_batch=4, max_len=64)
    prompts = [[5], [6, 7], [8, 9, 10, 11], [12, 13, 14]]
    for r, p in enumerate(prompts):
        eng.submit(Request(rid=r, prompt=p, max_new_tokens=4))
    done = eng.run_until_done()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert all(len(r.out) == 4 for r in done)
    # one compiled prefill signature per distinct prompt length (exact
    # lengths - padding would corrupt recurrent/ring-buffer state)
    assert len(eng._prefill_fns) == 4


def test_refilled_slot_decodes_like_fresh_engine():
    """Per-slot decode positions: a request seated by slot refill (other
    slots already decoded past its positions) must see exactly the cache
    rows and next-step logits it would see in a fresh engine. Compared on
    logits with tolerance - token ids of a random-init model flip on
    near-tie argmax under run-to-run float jitter."""
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    prompt = [9, 4, 7]

    fresh = DecodeEngine(cfg, params, max_batch=2, max_len=64)
    fresh.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=4))
    fresh._fill_slots()

    eng = DecodeEngine(cfg, params, max_batch=2, max_len=64)
    for r in range(2):
        eng.submit(Request(rid=r, prompt=[1 + r, 2], max_new_tokens=5))
    eng.submit(Request(rid=2, prompt=list(prompt), max_new_tokens=4))
    while not any(s is not None and s.done for s in eng.slots):
        eng.step()
    eng._fill_slots()          # seats rid=2 into a used slot
    slot = next(i for i, s in enumerate(eng.slots)
                if s is not None and s.rid == 2)
    assert eng._slot_pos[slot] == fresh._slot_pos[0]
    # the refilled slot's KV rows match a fresh engine's (junk from the
    # previous occupant is fully overwritten)
    for layer in ("tail_0", "tail_1"):
        np.testing.assert_allclose(
            np.asarray(eng._state["layers"][layer]["k"][slot]),
            np.asarray(fresh._state["layers"][layer]["k"][0]),
            atol=1e-5)
    # and the next decode step computes the same distribution
    lf, _ = lm.decode_step(params, cfg, fresh._state, fresh._toks,
                           jnp.asarray(fresh._slot_pos))
    le, _ = lm.decode_step(params, cfg, eng._state, eng._toks,
                           jnp.asarray(eng._slot_pos))
    np.testing.assert_allclose(np.asarray(le)[slot], np.asarray(lf)[0],
                               atol=1e-4)


def test_prefill_padding_is_inert():
    """Bucket padding must not change what a request conditions on: the
    engine's first-step logits for a non-power-of-two prompt equal an
    exact token-by-token reference decode."""
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    prompt = [9, 4, 7]              # L buckets to 4

    eng = DecodeEngine(cfg, params, max_batch=2, max_len=64)
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=1))
    eng._fill_slots()
    le, _ = lm.decode_step(params, cfg, eng._state, eng._toks,
                           jnp.asarray(eng._slot_pos))

    # reference: feed the prompt one token at a time, no padding
    state = lm.init_decode_state(cfg, 1, 64)
    for t, tok in enumerate(prompt[:-1]):
        _, state = lm.decode_step(params, cfg, state,
                                  jnp.asarray([tok], jnp.int32),
                                  jnp.int32(t))
    lr, _ = lm.decode_step(params, cfg, state,
                           jnp.asarray([prompt[-1]], jnp.int32),
                           jnp.int32(len(prompt) - 1))
    np.testing.assert_allclose(np.asarray(le)[0], np.asarray(lr)[0],
                               atol=1e-4)


def test_request_latency_accounting():
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(cfg, params, max_batch=2, max_len=64)
    for r in range(3):
        eng.submit(Request(rid=r, prompt=[1, 2, 3], max_new_tokens=2))
    obs.reset()
    obs.enable()
    try:
        done = eng.run_until_done()
        steps = [e for e in obs.tracer().events()
                 if e["name"] == "engine.step"]
    finally:
        obs.reset()
    for r in done:
        assert r.t_submit is not None and r.t_done is not None
        assert r.t_submit <= r.t_start <= r.t_first_token <= r.t_done
        assert r.latency_s >= 0 and r.queue_wait_s >= 0
    # two slots, three requests of two tokens: seat two, finish them,
    # seat the third, finish it
    assert [(e["args"]["active"], e["args"]["seated"]) for e in steps] \
        == [(2, 2), (2, 0), (1, 1), (1, 0)]
    assert all(e["cat"] == "engine" and e["dur"] > 0 for e in steps)


def test_tiered_matmul_matches_dense():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.5, (32, 64)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (4, 32)), jnp.float32)
    counts = {"hp_bf16": 16, "hp_int8": 16, "lp_bf16": 16, "lp_int8": 16}
    segs = split_weight(w, counts)
    y = tiered_matmul(x, segs)
    ref = x @ w
    # int8 segments introduce bounded quantization error
    rel = float(jnp.abs(y - ref).max() / jnp.abs(ref).max())
    assert rel < 0.08


def test_tiered_matmul_custom_int8_tiers_matches_dense():
    """Substrate-declared tier plans (the cxl int8/int8 pairs and the
    3-way cxl-tier-3 split) flow through split_weight/tiered_matmul via
    the formats mapping."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(0, 0.5, (24, 48)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (3, 24)), jnp.float32)
    counts = {"hbm_int8": 20, "ddr_int8": 16, "cxl_int8": 12}
    formats = {t: "int8" for t in counts}
    segs = split_weight(w, counts, formats=formats)
    assert set(segs) == set(counts)
    assert all("q" in s for s in segs.values())     # all-int8 tiers
    y = tiered_matmul(x, segs)
    ref = x @ w
    rel = float(jnp.abs(y - ref).max() / jnp.abs(ref).max())
    assert rel < 0.08
    # re-tiering = moving columns between int8 segments: same math
    moved = split_weight(w, {"hbm_int8": 4, "ddr_int8": 4, "cxl_int8": 40},
                         formats=formats)
    y2 = tiered_matmul(x, moved)
    rel2 = float(jnp.abs(y2 - ref).max() / jnp.abs(ref).max())
    assert rel2 < 0.08


def test_tiered_all_bf16_is_near_exact():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(0, 0.5, (16, 24)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (3, 16)), jnp.float32)
    segs = split_weight(w, {"hp_bf16": 12, "hp_int8": 0, "lp_bf16": 12,
                            "lp_int8": 0})
    y = tiered_matmul(x, segs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=3e-2,
                               atol=3e-2)   # bf16 rounding only


def test_hetero_engine_adapts_and_meets_deadlines():
    cfg = _tiny_cfg()
    params = lm.init_lm(jax.random.PRNGKey(1), cfg)
    eng = HeteroServeEngine(cfg, params, t_slice_ms=200.0, max_batch=4)
    hi = eng.run_slice(8)
    lo = eng.run_slice(1)
    lo2 = eng.run_slice(1)
    assert hi.report.deadline_met and lo.report.deadline_met
    # placement adapts: low load shifts weight share to the LP pool
    hp_hi = sum(v for k, v in hi.report.placement.items()
                if k.startswith("hp"))
    hp_lo = sum(v for k, v in lo2.report.placement.items()
                if k.startswith("hp"))
    assert hp_lo <= hp_hi
    # per-task energy lower at low load
    e_hi = hi.report.energy_pj / hi.report.n_tasks
    e_lo = lo2.report.energy_pj / lo2.report.n_tasks
    assert e_lo < e_hi * 1.5
    assert eng.energy_uj() > 0
    # the tiered weights actually changed format
    assert eng._tiered is not None
    x = jnp.ones((2, cfg.d_model), jnp.float32)
    y = eng.tiered_forward(x)
    assert y.shape == (2, cfg.d_ff)


def test_decode_step_reads_positions_it_was_given():
    """The per-slot positions handed to the asynchronously dispatched
    step must not alias the host array the engine bumps right after."""
    cfg = _tiny_cfg()
    eng = DecodeEngine(cfg, lm.init_lm(jax.random.PRNGKey(0), cfg),
                       max_batch=2, max_len=16)
    # the CPU client wraps a 64-byte-aligned host array without copying;
    # align the positions so that an aliasing engine fails every time
    buf = np.zeros(64, np.int32)
    start = (-buf.ctypes.data % 64) // buf.itemsize
    eng._slot_pos = buf[start:start + 2]
    seen = []
    step = eng._step_fn

    def spy(p, st, tk, pos):
        seen.append(pos)
        return step(p, st, tk, pos)

    eng._step_fn = spy
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    eng.step()
    # the last prompt token decodes at position len(prompt) - 1
    assert int(np.asarray(seen[0])[0]) == 2
    assert int(eng._slot_pos[0]) == 3


def test_hetero_engine_requires_its_config():
    params = lm.init_lm(jax.random.PRNGKey(1), _tiny_cfg())
    with pytest.raises(ValueError, match="ModelConfig"):
        HeteroServeEngine(None, params, t_slice_ms=200.0)


def test_hetero_engine_tiers_every_layer_of_a_scanned_stack():
    """The published configs scan their layers (stacked weights with a
    leading layer axis): each layer's up/gate matrix is tiered."""
    import dataclasses
    cfg = dataclasses.replace(_tiny_cfg(), n_layers=3, scan_layers=True)
    params = lm.init_lm(jax.random.PRNGKey(1), cfg)
    assert "scan" in params["stack"]
    eng = HeteroServeEngine(cfg, params, t_slice_ms=200.0, max_batch=4)
    eng.run_slice(2)
    assert sorted(eng._tiered) == sorted(
        (f"scan.{g}.p0", w) for g in range(3) for w in ("w_up", "w_gate"))
    x = jnp.ones((2, cfg.d_model), jnp.float32)
    y = eng.tiered_forward(x)
    dense = x @ params["stack"]["scan"]["p0"]["ffn"]["w_up"][0]
    assert float(jnp.abs(y - dense).max() / jnp.abs(dense).max()) < 0.08


def test_tpu_arch_spaces_sane():
    arch = tpu_arch(4, 4)
    names = {s.name for s in arch.spaces}
    assert names == {"hp_mram", "hp_sram", "lp_mram", "lp_sram"}
    hp_s = arch.cluster("hp").space("sram")
    hp_m = arch.cluster("hp").space("mram")
    # bf16 reads twice the bytes of int8
    assert hp_s.mem.read_ns == pytest.approx(2 * hp_m.mem.read_ns)
    # volatile bf16 residency pins idle power; int8 sleeps
    assert hp_s.mem.volatile and not hp_m.mem.volatile
    assert hp_s.mem.static_mw > hp_m.mem.static_mw
    # LP pool is slower per op
    lp = tpu_arch(4, 4).cluster("lp")
    assert lp.pe.op_ns > arch.cluster("hp").pe.op_ns
