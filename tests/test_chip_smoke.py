"""``chip_smoke.py`` rehearsed on the CPU, and its refusals.

The phases run at the smoke size with the kernels in interpret mode,
on a scanned bfloat16 stack like the published config's, so the chip
run exercises nothing this test has not driven through the same code.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_rehearse_at_smoke_size(chip_smoke, monkeypatch, capsys):
    monkeypatch.setenv("REPRO_LUT_BACKEND", "pallas_interpret")
    cfg = dataclasses.replace(get_smoke_config(chip_smoke.ARCH),
                              scan_layers=True, dtype=jnp.bfloat16)
    times = chip_smoke.run_phases(cfg, pim_backend="pallas_interpret",
                                  expect_backend="pallas_interpret")
    assert list(times) == ["init", "a_serve", "b_requests", "c_lut",
                           "d_pim_mac"]
    out = capsys.readouterr().out
    assert out.count("  request ") == 4
    assert "tpu-pool: backend=pallas_interpret" in out
    assert "cxl-tier-3: backend=pallas_interpret" in out


def test_phase_check_fails_loudly(chip_smoke, monkeypatch):
    """A LUT built on another backend than the expected one fails the
    phase instead of passing on a fallback."""
    monkeypatch.delenv("REPRO_LUT_BACKEND", raising=False)
    cfg = get_smoke_config(chip_smoke.ARCH)
    with pytest.raises(chip_smoke.PhaseError, match="expected pallas"):
        chip_smoke.phase_lut(cfg, expect="pallas", substrates=("tpu-pool",))


def test_main_refuses_cpu_and_names_it(chip_smoke, monkeypatch, capsys):
    for var in chip_smoke.BACKEND_ENVS:
        monkeypatch.delenv(var, raising=False)
    assert chip_smoke.main() == 1
    cap = capsys.readouterr()
    assert "platform 'cpu'" in cap.err
    assert '"ok"' not in cap.out


@pytest.mark.parametrize("var", ["REPRO_LUT_BACKEND",
                                 "REPRO_KNAPSACK_BACKEND"])
def test_main_refuses_backend_override(chip_smoke, monkeypatch, capsys,
                                       var):
    monkeypatch.setenv(var, "pallas_interpret")
    assert chip_smoke.main() == 2
    cap = capsys.readouterr()
    assert var in cap.err and '"ok"' not in cap.out


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == tmp_path


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.cache_dir()
    assert path == ROOT / ".jax_cache"
    assert f"{path.name}/" in (ROOT / ".gitignore").read_text().split()
