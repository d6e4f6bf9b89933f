"""Public op: the fused on-device LUT pipeline, kernel- or ref-backed.

This is the production build engine behind ``build_lut(method="dp",
batched=True)`` (repro.core.placement) and the clock-grid batched
``build_lut_grid``: per-cluster Algorithm-1 stage tables, the consulted
t-grid row gather, and the Algorithm-2 min-plus combine with argmin
backtrace, all in one device launch per build - instead of one
``knapsack_dp`` dispatch per cluster plus a host numpy fold per build.
The backends are

  * ``pallas``           - the fused TPU kernel (kernel.py), one
    ``pallas_call`` over the (variant, cluster, space, K-panel) grid,
  * ``pallas_interpret`` - the same kernel under the Pallas interpreter,
    so the fused path (including the K-panel carry chain) is exercised
    end-to-end on CPU runners (CI),
  * ``ref``              - the jitted pure-jnp oracle (ref.py), the CPU
    production backend.

``backend="auto"`` resolves to ``pallas`` on TPU and ``ref`` elsewhere;
the ``REPRO_LUT_BACKEND`` environment variable overrides the auto
choice. All backends return byte-identical float32 tables and identical
integer splits (asserted by tests/test_lut_pipeline.py), so backend
choice never changes a LUT entry.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.lut_pipeline.kernel import lut_pipeline_pallas
from repro.kernels.lut_pipeline.ref import lut_pipeline_ref

BACKEND_ENV = "REPRO_LUT_BACKEND"

BACKENDS = ("ref", "pallas", "pallas_interpret")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str = "auto") -> str:
    """Resolve ``auto`` to a concrete backend (env override wins) and
    validate the result, so a typo'd env value fails with the valid
    names instead of an opaque lowering error."""
    if backend == "auto":
        backend = (os.environ.get(BACKEND_ENV)
                   or ("pallas" if _on_tpu() else "ref"))
    if backend not in BACKENDS:
        raise ValueError(f"unknown lut_pipeline backend {backend!r}; "
                         f"one of {BACKENDS} (or 'auto', env var "
                         f"{BACKEND_ENV})")
    return backend


def lut_build(t_items, e_items, T: int, K: int, rows, *,
              backend: str = "auto", bk: int = 128):
    """Fused Algorithm-1 + Algorithm-2 evaluation, batched over variants.

    Args:
      t_items: (V, C, n) per-variant/cluster/space integer tick costs.
        Ragged clusters must be inert-padded with ``(t=1, e=+inf)``; an
        infinite-cost space folds to a bitwise copy of the previous
        stage, so padding changes no byte of any result (and the
        placement backtrace walks through padded stages via its
        carry branch).
      e_items: (V, C, n) per-space energies (pad ``+inf``).
      T, K: tick horizon / weight-group count; tables are (T+1, K+1).
      rows: (R,) or (V, R) consulted t-grid tick rows, ``0 <= row <= T``.
      backend: "auto" | "pallas" | "pallas_interpret" | "ref".
      bk: K-panel width of the pallas kernel.

    Returns:
      stages: (V, C, n+1, T+1, K+1) float32 per-space DP stage tables,
        stage 0 being the k=0 base - the same layout
        ``knapsack_dp(..., return_stages=True)`` yields per cluster,
        ready for ``placement.backtrace_tables``.
      min_e:  (V, R) float32 min total energy per consulted row.
      splits: (V, R, C) int32 optimal per-cluster group counts
        (-1 on infeasible rows), bit-matching the numpy
        ``combine_many`` fold of the same tables.
    """
    backend = resolve_backend(backend)
    t = jnp.asarray(t_items, jnp.int32)
    e = jnp.asarray(e_items, jnp.float32)
    if t.ndim != 3 or e.shape != t.shape:
        raise ValueError(f"t_items/e_items must both be (V, C, n), got "
                         f"{t.shape} and {e.shape}")
    V = t.shape[0]
    r = jnp.asarray(rows, jnp.int32)
    if r.ndim == 1:
        r = jnp.broadcast_to(r[None, :], (V, r.shape[0]))
    if backend == "ref":
        stages, min_e, splits = lut_pipeline_ref(t, e, r, T=T, K=K)
    else:
        stages, min_e, splits = lut_pipeline_pallas(
            t, e, r, T=T, K=K, bk=bk,
            interpret=(backend == "pallas_interpret"))
    base = jnp.full((V, t.shape[1], 1, T + 1, K + 1), jnp.inf, jnp.float32)
    base = base.at[..., 0].set(0.0)
    stages = jnp.concatenate([base, stages], axis=2)
    if obs.enabled():
        # dispatch accounting keyed by the RESOLVED backend, so a trace
        # shows whether the kernel, interpreter or ref path actually ran
        obs.counter("kernels.lut_pipeline.dispatch", backend=backend)
    return stages, min_e, splits
