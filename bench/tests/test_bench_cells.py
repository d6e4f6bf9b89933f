"""Each cell's serving loop rehearsed at smoke size on the CPU, through
the harness functions a run calls; the CLI itself refuses the CPU; and
the comparison that decides ``correct`` catches a broken timed path and
the int8 and fp8 controls."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

import tiny  # puts bench/ on the path
import harness
import reference
import traffic

SEED = 2 ** 33 + 5
ROOT = os.path.dirname(tiny.BENCH)


@pytest.mark.parametrize("mix", ["decode_long_16", "decode_long_32"])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(mix, trace):
    """The loop and the line it prints. ``correct`` is not asserted: on
    the CPU, ``DecodeEngine.step`` can race with its own slot positions
    under load (the device copy of the position vector reads the host
    array after the step has bumped it), which sends wrong tokens; that
    is the program's fault, not the loop's."""
    cell = tiny.cell(mix)
    r = harness.run_cell(cell, SEED, 1.5, trace, time.perf_counter())
    assert set(r["checks"]) == {"max_gap", "mean_gap", "wrong_answers"}
    assert r["checks"]["max_gap"]["value"] >= \
        r["checks"]["mean_gap"]["value"] >= 0
    assert r["checks"]["wrong_answers"]["value"] == 0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    assert r["notes"]["window_compiles"] == 0
    assert r["notes"]["steps"] > 0 and r["notes"]["controls"] == {}
    if trace:
        assert "device_idle_share" in r["metrics"]
        assert r["device"]["window_s"] > 0
    else:
        assert r["metrics"]["out_tok_s"]["value"] > 0
        assert r["metrics"]["setup_s"]["value"] > 0


def _state_unchanged(orig):
    def step(params, cfg, state, tokens, pos):
        logits, _ = orig(params, cfg, state, tokens, pos)
        return logits, state
    return step


def _token_altered(orig):
    def step(params, cfg, state, tokens, pos):
        logits, st = orig(params, cfg, state, tokens, pos)
        return jnp.roll(logits, 1, axis=-1), st
    return step


def _half_batch(orig):
    def step(params, cfg, state, tokens, pos):
        logits, st = orig(params, cfg, state, tokens, pos)
        h = logits.shape[0] // 2
        if h:
            logits = logits.at[h:].set(logits[:h][: logits.shape[0] - h])
        return logits, st
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_batch])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.models import lm
    monkeypatch.setattr(lm, "decode_step", fault(lm.decode_step))
    r = harness.run_cell(tiny.cell("decode_long_16"), SEED, 1.0, False,
                         time.perf_counter())
    assert not r["correct"], r["checks"]


def test_a_run_judges_its_controls_by_its_own_limits():
    r = harness.run_cell(tiny.cell("decode_long_16"), SEED, 1.0, False,
                         time.perf_counter(), controls=("int8", "fp8"))
    for q in ("int8", "fp8"):
        c = r["notes"]["controls"][q]
        assert set(c["checks"]) == {"max_gap", "mean_gap"}
        assert c["correct"] == harness.passes(c["checks"])
        assert all(v["limit"] == tiny.LIMITS[k]
                   for k, v in c["checks"].items())


@pytest.fixture(scope="module")
def weights():
    """At a size a test can hold (24 layers of width 512, 8,192 ids):
    the weights, and two 256-token sequences of seeded ids."""
    import model
    conf = tiny.conf()
    conf["config"].update(hidden_size=512, intermediate_size=2048,
                          vocab_size=8192, num_hidden_layers=24)
    conf["fixed"] = {"head_dim": 64, "rotary_dim": 64}
    d = model.dims(conf)
    rng = traffic.rng_for(SEED, 5)
    seqs = [(rng.integers(1, 8192, 8).tolist(),
             rng.integers(1, 8192, 248).tolist()) for _ in range(2)]
    return model.make_weights(d, SEED), d, seqs


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("cell", ["internlm2_1_8b.decode_long",
                                  "chatglm3_6b.decode_long"])
def test_control_is_not_correct_at_the_cells_limits(weights, quant, cell):
    """The control, put in the program's place on the same tokens, puts
    other tokens first, and the run's own comparison against the cell's
    committed limits finds it not correct."""
    w, d, seqs = weights
    with open(os.path.join(tiny.BENCH, "cells", f"{cell}.json")) as f:
        limits = json.load(f)["limits"]
    ctl = harness.judge(harness._gap_readings(reference.gaps(
        w, d, seqs, pad_to=256, rows=2, quant=quant)), limits)
    assert ctl["max_gap"]["value"] > 0
    assert not harness.passes(ctl), ctl


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_cli_refuses_the_cpu():
    p = _cli(ROOT, "--workload", "internlm2_1_8b.decode_long", "--seed",
             str(SEED), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "TPU" in p.stderr and p.stdout.strip() == ""


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = _cli(tmp_path, "--workload", cell, "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
