"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles a kernel at the sizes the
full-width ``internlm2_1_8b`` serving path uses, for a ``v5e:2x2``
topology that is described, not attached, and asserts the Mosaic kernel
is in the compiled program (``tpu_custom_call``). This catches what the
interpret-mode tests cannot: constructs Mosaic refuses and VMEM
overruns. The topology is described inside a module fixture, so only the
worker that runs this file loads the TPU compiler; the fixture skips
where no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.configs import get_config
from repro.kernels.knapsack_dp.kernel import dp_space_update_pallas
from repro.kernels.lut_pipeline import ops as lut_ops
from repro.kernels.lut_pipeline.kernel import lut_pipeline_pallas
from repro.kernels.pim_mac.kernel import pim_matmul_pallas


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


class _Captured(Exception):
    pass


def _lut_problem(substrate: str):
    """(V, C, n, T, K, R) of the fused LUT build that
    ``api.lut(substrate, <full-width internlm2_1_8b>, solver="dp")``
    launches, read off the op's arguments (the build stops there)."""
    def capture(t_items, e_items, T, K, rows, **_):
        V, C, n = t_items.shape
        raise _Captured((V, C, n, T, K, rows.shape[-1]))

    orig = lut_ops.lut_build
    lut_ops.lut_build = capture
    try:
        api.lut(substrate, get_config("internlm2_1_8b"), solver="dp")
    except _Captured as c:
        return c.args[0]
    finally:
        lut_ops.lut_build = orig
    raise AssertionError("the dp build never reached the fused op")


@pytest.mark.parametrize("d_in,d_out", [(2048, 8192), (8192, 2048)],
                         ids=["up_gate", "down"])
def test_pim_mac_compiles_at_ffn_widths(one_chip, d_in, d_out):
    """The W8A8 kernel over a full-width FFN weight (one 128-row
    activation tile)."""
    M = 128
    c = pim_matmul_pallas.lower(
        _sds((M, d_in), jnp.int8, one_chip),
        _sds((d_in, d_out), jnp.int8, one_chip),
        _sds((M,), jnp.float32, one_chip),
        _sds((d_out,), jnp.float32, one_chip)).compile()
    _assert_kernel(c)


@pytest.mark.parametrize("substrate,clusters", [("tpu-pool", 2),
                                                ("cxl-tier-3", 3)])
def test_lut_pipeline_compiles_at_serving_sizes(one_chip, substrate,
                                                clusters):
    V, C, n, T, K, R = _lut_problem(substrate)
    assert C == clusters and K == 256 and T > 2048
    c = jax.jit(lambda t, e, r: lut_pipeline_pallas(t, e, r, T=T, K=K)
                ).lower(_sds((V, C, n), jnp.int32, one_chip),
                        _sds((V, C, n), jnp.float32, one_chip),
                        _sds((V, R), jnp.int32, one_chip)).compile()
    _assert_kernel(c)


def test_lut_pipeline_compiles_at_tick_cap(one_chip):
    """The largest tick horizon ``placement._dp_problem`` allows
    (T = 16384) with a three-cluster fold: the VMEM ceiling."""
    T, K, R = 16384, 256, 33
    c = jax.jit(lambda t, e, r: lut_pipeline_pallas(t, e, r, T=T, K=K)
                ).lower(_sds((1, 3, 2), jnp.int32, one_chip),
                        _sds((1, 3, 2), jnp.float32, one_chip),
                        _sds((1, R), jnp.int32, one_chip)).compile()
    _assert_kernel(c)


def test_knapsack_dp_compiles(one_chip):
    """One space fold of the unfused knapsack kernel at the default
    tick count (T = 2048) over one 512-lane panel."""
    c = jax.jit(lambda dp, t, e: dp_space_update_pallas(dp, t_i=t, e_i=e)
                ).lower(_sds((2049, 257), jnp.float32, one_chip),
                        _sds((), jnp.int32, one_chip),
                        _sds((), jnp.float32, one_chip)).compile()
    _assert_kernel(c)
