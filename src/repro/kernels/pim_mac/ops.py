"""Public op wrapper for the PIM-MAC kernel.

``pim_matmul`` pads arbitrary shapes to block multiples, dispatches to the
Pallas kernel on TPU (or ``interpret=True`` for CPU validation) and to the
pure-jnp oracle elsewhere - the math is identical, so models built on this
op lower cleanly in the CPU dry-run while targeting the kernel on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.pim_mac.kernel import pim_matmul_pallas
from repro.kernels.pim_mac.ref import pim_matmul_ref


def _pad_to(x: jnp.ndarray, mult0: int, mult1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pim_matmul(x_i8: jnp.ndarray, w_i8: jnp.ndarray,
               scale_x: jnp.ndarray, scale_w: jnp.ndarray, *,
               bm: int = 128, bn: int = 128, bk: int = 128,
               out_dtype=jnp.float32, backend: str = "auto") -> jnp.ndarray:
    """W8A8 matmul with per-row/col scales; any (M, K) x (K, N) shapes.

    backend: "auto" (pallas on TPU, ref elsewhere), "pallas",
             "pallas_interpret" (kernel body on CPU), or "ref".

    Backend resolution and dispatch accounting stay OUTSIDE the jit so
    every call is counted (the jitted body only runs at trace time);
    the resolved backend is a static argname, so the compile cache is
    unchanged.
    """
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if obs.enabled():
        obs.counter("kernels.pim_mac.dispatch", backend=backend)
    return _pim_matmul_impl(x_i8, w_i8, scale_x, scale_w, bm=bm, bn=bn,
                            bk=bk, out_dtype=out_dtype, backend=backend)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "backend"))
def _pim_matmul_impl(x_i8: jnp.ndarray, w_i8: jnp.ndarray,
                     scale_x: jnp.ndarray, scale_w: jnp.ndarray, *,
                     bm: int, bn: int, bk: int, out_dtype,
                     backend: str) -> jnp.ndarray:
    M, K = x_i8.shape
    _, N = w_i8.shape
    scale_x = jnp.broadcast_to(jnp.asarray(scale_x, jnp.float32).reshape(-1),
                               (M,))
    scale_w = jnp.broadcast_to(jnp.asarray(scale_w, jnp.float32).reshape(-1),
                               (N,))
    if backend == "ref":
        return pim_matmul_ref(x_i8, w_i8, scale_x, scale_w, out_dtype)

    interpret = backend == "pallas_interpret"
    xp = _pad_to(x_i8, bm, bk)
    wp = _pad_to(w_i8, bk, bn)
    sxp = jnp.pad(scale_x, (0, (-M) % bm))
    swp = jnp.pad(scale_w, (0, (-N) % bn))
    out = pim_matmul_pallas(xp, wp, sxp, swp, bm=bm, bn=bn, bk=bk,
                            out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]
