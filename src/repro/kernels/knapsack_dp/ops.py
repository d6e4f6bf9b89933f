"""Public op: per-cluster Algorithm-1 DP table, kernel- or ref-backed.

This is the production table builder behind ``build_lut(method="dp")``
(repro.core.placement): the per-space fold runs on one of

  * ``pallas``           - the TPU kernel (kernel.py),
  * ``pallas_interpret`` - the same kernel under the Pallas interpreter,
    so the kernel *code path* is exercised on CPU runners (CI),
  * ``ref``              - the jitted pure-jnp oracle (ref.py), the CPU
    production backend.

``backend="auto"`` resolves to ``pallas`` on TPU and ``ref`` elsewhere;
the ``REPRO_KNAPSACK_BACKEND`` environment variable overrides the auto
choice (CI sets it to ``pallas_interpret`` to test the kernel path on
CPU runners, where auto would otherwise never select it).

``return_stages=True`` returns the stacked per-space tables
``(n+1, T+1, K+1)`` (stage 0 is the k=0 base table) that
``repro.core.placement.backtrace_tables`` walks to recover placements.
"""
from __future__ import annotations

import os
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.knapsack_dp.kernel import dp_space_update_pallas
from repro.kernels.knapsack_dp.ref import dp_space_update_ref

BACKEND_ENV = "REPRO_KNAPSACK_BACKEND"

# t_i / e_i passed as traced scalars => one compile per table shape, not
# one per (t_i, e_i) value (the LUT builder folds 2 spaces per cluster
# with different costs).
_ref_fold = jax.jit(dp_space_update_ref)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


BACKENDS = ("ref", "pallas", "pallas_interpret")


def resolve_backend(backend: str = "auto") -> str:
    """Resolve ``auto`` to a concrete backend (env override wins) and
    validate the result, so a typo'd env value fails with the valid
    names instead of an opaque lowering error."""
    if backend == "auto":
        backend = (os.environ.get(BACKEND_ENV)
                   or ("pallas" if _on_tpu() else "ref"))
    if backend not in BACKENDS:
        raise ValueError(f"unknown knapsack_dp backend {backend!r}; "
                         f"one of {BACKENDS} (or 'auto', env var "
                         f"{BACKEND_ENV})")
    return backend


def knapsack_dp(t_items: Sequence[int], e_items: Sequence[float],
                T: int, K: int, *, backend: str = "auto",
                bk: int = 512, return_stages: bool = False) -> jnp.ndarray:
    """Build the (T+1, K+1) min-energy table for one cluster's spaces.

    backend: "auto" | "pallas" | "pallas_interpret" | "ref".
    return_stages: also return every intermediate per-space table,
      stacked to (n+1, T+1, K+1), for backtracing placements.
    """
    backend = resolve_backend(backend)
    dp = jnp.full((T + 1, K + 1), jnp.inf, dtype=jnp.float32)
    dp = dp.at[:, 0].set(0.0)
    stages = [dp]
    for t_i, e_i in zip(t_items, e_items):
        if backend == "ref":
            dp = _ref_fold(dp, jnp.int32(t_i), jnp.float32(e_i))
        else:
            # t_i/e_i are traced operands here too (SMEM scalars in the
            # kernel): one compile per table shape, not per cost value
            dp = dp_space_update_pallas(
                dp, t_i=jnp.int32(t_i), e_i=jnp.float32(e_i), bk=bk,
                interpret=(backend == "pallas_interpret"))
        if return_stages:
            stages.append(dp)
    if obs.enabled():
        # dispatch accounting keyed by the RESOLVED backend, so a trace
        # shows whether the kernel, interpreter or ref path actually ran
        obs.counter("kernels.knapsack_dp.dispatch", backend=backend)
    if return_stages:
        return jnp.stack(stages)
    return dp
