"""What DecodeEngine keeps on the device. It serves a copy of its weights
cast once to the compute dtype: the step, the prefill and the served
tokens equal those computed from the caller's float32 weights bit for bit,
leaves read in float32 keep float32, and the caller's tree is left as it
was. A refill writes the prompt state into the engine's state in place."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import lm
from repro.serve.engine import DecodeEngine, Request

# DecodeEngine decodes without an encoder, so it serves every registered
# architecture but the encoder-decoder ones
SERVED = [a for a in ARCH_IDS if not get_smoke_config(a).is_encdec]
PROMPTS = ([3, 4, 5], [7, 8, 9, 10, 11])
# what the blocks read in float32: norm gains, recurrent decay, gate biases
READ_IN_FLOAT32 = {"ln1", "ln2", "final_ln", "lam", "b_a", "b_x", "bf", "bi"}


def _bf16(arch):
    return dataclasses.replace(get_smoke_config(arch), dtype=jnp.bfloat16)


def _weights(cfg):
    """Seeded weights with every leaf moved off bfloat16's grid (norm
    gains and gate biases start at values bfloat16 holds exactly, which
    would hide a cast of a leaf read in float32)."""
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    flat, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(flat))
    return jax.tree_util.tree_unflatten(treedef, [
        x + 0.01 * jax.random.normal(k, x.shape, x.dtype)
        for k, x in zip(keys, flat)])


def _leaves_with_names(tree):
    return [(getattr(path[-1], "key", None), x) for path, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b), strict=True):
        assert x.dtype == y.dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _serve(eng):
    for rid, prompt in enumerate(PROMPTS):
        eng.submit(Request(rid=rid, prompt=list(prompt), max_new_tokens=3))
    return {r.rid: r.out for r in eng.run_until_done()}


@pytest.mark.parametrize("arch", SERVED)
def test_engine_serves_what_the_float32_weights_compute(arch):
    cfg = _bf16(arch)
    params = _weights(cfg)
    eng = DecodeEngine(cfg, params, max_batch=2, max_len=16)
    prompts = jnp.asarray([PROMPTS[1], PROMPTS[1][::-1]], jnp.int32)
    prefill = eng._prefill_fn(2, prompts.shape[1])
    state = prefill(eng.params, prompts)
    _assert_trees_equal(state, prefill(params, prompts))
    toks = prompts[:, -1]
    pos = jnp.full((2,), prompts.shape[1] - 1, jnp.int32)
    logits, st = eng._step_fn(eng.params, state, toks, pos)
    ref_logits, ref_st = jax.jit(lm.decode_step, static_argnums=1)(
        params, cfg, state, toks, pos)
    assert np.array_equal(np.asarray(logits), np.asarray(ref_logits))
    _assert_trees_equal(st, ref_st)
    # the engine as it served before: the caller's weights at each launch
    ref = DecodeEngine(cfg, params, max_batch=2, max_len=16)
    ref.params = params
    assert _serve(eng) == _serve(ref)
    assert np.array_equal(np.asarray(eng.last_logits),
                          np.asarray(ref.last_logits))


@pytest.mark.parametrize("arch", ["internlm2_1_8b", "recurrentgemma_2b",
                                  "xlstm_1_3b", "arctic_480b"])
def test_engine_holds_compute_dtype_weights(arch):
    cfg = _bf16(arch)
    params = _weights(cfg)
    eng = DecodeEngine(cfg, params, max_batch=2, max_len=16)
    served = _leaves_with_names(eng.params)
    for (name, mine), (_, x) in zip(_leaves_with_names(params), served,
                                    strict=True):
        assert mine.dtype == jnp.float32      # the caller's tree as it was
        assert not mine.is_deleted()
        np.asarray(mine)
        if name in READ_IN_FLOAT32:
            assert x is mine                  # read in float32: not copied
        else:
            assert x.dtype == jnp.bfloat16
    assert {"ln1", "final_ln", "embed", "lm_head"} <= {n for n, _ in served}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_weights_in_compute_dtype_are_served_as_they_are(dtype):
    cfg = dataclasses.replace(get_smoke_config("internlm2_1_8b"),
                              dtype=dtype)
    params = lm.compute_params(_weights(cfg), cfg)
    assert not any(jax.tree_util.tree_leaves(lm.compute_casts(params, cfg)))
    eng = DecodeEngine(cfg, params, max_batch=2, max_len=16)
    for mine, x in zip(jax.tree_util.tree_leaves(params),
                       jax.tree_util.tree_leaves(eng.params), strict=True):
        assert x is mine


def test_a_refill_writes_the_engine_state_in_place():
    cfg = _bf16("internlm2_1_8b")
    eng = DecodeEngine(cfg, _weights(cfg), max_batch=2, max_len=16)
    old = jax.tree_util.tree_leaves(eng._state)
    eng.submit(Request(rid=0, prompt=[3, 4, 5], max_new_tokens=2))
    eng._fill_slots()
    assert all(x.is_deleted() for x in old)      # donated, not copied
    assert all(not x.is_deleted()
               for x in jax.tree_util.tree_leaves(eng.params))
