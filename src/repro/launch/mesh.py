"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (assignment requirement).

Single pod : (data=16, model=16)            - 256 chips (TPU v5e pod).
Multi-pod  : (pod=2, data=16, model=16)     - 512 chips across 2 pods; the
"pod" axis carries pure data parallelism (params replicated per pod, grads
all-reduced across the DCI), matching how real multi-pod training slices.

Every axis is ``AxisType.Auto``: ``jax.make_mesh`` defaults to Explicit
axes, under which a data-sharded batch split into microbatches reaches
``lax.scan`` sharded on its leading axis and is refused. The sharding
rules (``repro.parallel.sharding``) are written for the Auto propagator.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small host-device mesh for CPU integration tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count set by the caller's
    process, NOT globally)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
