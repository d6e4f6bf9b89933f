"""Pallas TPU kernel for the fused LUT pipeline (one launch per build).

The unfused dp path runs the ``knapsack_dp`` kernel per cluster and then
folds the gathered tables on the host (numpy ``combine_many``) - one
device<->host round-trip per build stage. This kernel keeps the whole
Algorithm-1 + Algorithm-2 pipeline resident: a single ``pallas_call``
walks the grid

    (v, c, i, p)  =  variant x cluster x space x K-panel,

row-major (sequential on TPU), so scratch persists across steps and acts
as the dataflow spine:

  * ``S``     (P, T+1, bk) rolling stage buffer, one slab per K-panel:
               at step ``(c, i, p)`` panels ``>= p`` still hold stage
               ``i-1``, panels ``< p`` already hold stage ``i`` - exactly
               the knapsack kernel's panel chain, batched over clusters
               and variants. The k-1 carry across K-panels is read from
               the last column of panel ``p-1`` (+inf at ``p == 0``);
  * ``G``     (P, Rp, bk) the current cluster's final table gathered at
               the consulted t-grid rows (filled panel-by-panel during
               the last space);
  * ``F``     (Rp, Kp)   Algorithm-2 fold accumulator across clusters;
  * ``A``     (C-2, Rp, Kp) argmin traces of the middle folds, for the
               in-kernel split backtrace.

Each ``(v, c, i, p)`` step seeds its stage-output block from the
previous stage (the k=0 base pattern when ``i == 0``, the ``S`` panel
otherwise) and runs the t-recurrence in place - reads of row ``t``
see the previous stage, reads of row ``t - t_i < t`` see the updated
rows, matching the knapsack kernel's separate in/out panels bit for bit.
At the last panel of the last space of each cluster the kernel folds
``G`` into ``F`` and at the last cluster it runs the final k=K combine
plus the argmin backtrace and emits the per-variant ``min_e`` /
``splits`` outputs. The fold, the final combine and the backtrace are
``repro.core.multipool``'s ``minplus_fold_jnp``, ``final_combine_jnp``
and ``backtrace_splits_jnp`` - the functions the ref backend jits - so
both backends evaluate the same float additions in the same order.

What lowers where: Mosaic takes a traced index on a leading or a row
axis but not on the lane axis, so S and G keep the K-panel as a leading
axis; the row gather reads the stage block with a ``pl.ds`` row index
from SMEM (never indexes a loaded value); a column
at a traced lane index is a one-hot reduction and a lane shift is
``pltpu.roll``, so there is no ``dynamic_slice`` and no ``rev`` in the
kernel. Tables past column K are lane padding, never read for k <= K.

VMEM (bytes, f32/int32 = 4 B, Kp = P * bk):

    4 * ( 2 * (T+1) * bk            stage block, double-buffered
        +     (T+1) * Kp            S
        + (2 + max(C-2, 1)) * Rp*Kp G, F, A
        + 2 * 2 * Rp * 128 )        min_e / splits blocks

The full-width ``internlm2_1_8b`` builds use K = 256 (Kp = 384 at the
default bk = 128) and R = 33 (Rp = 40): T = 13028 on ``tpu-pool`` is
33.6 MB, T = 8433 on ``cxl-tier-3`` 21.9 MB, and the tick cap
T = 16384 of ``placement._dp_problem`` 42.2 MB. All are above the
default scoped VMEM limit, so ``vmem_limit_bytes`` is set from this sum
plus 8 MiB of headroom for Mosaic's own scratch; a build past
``VMEM_CAP`` fails with this arithmetic in the error instead of at
Mosaic compile time. (bk = 512 would double-buffer a (T+1, 512) block
and need about 80 MB at T = 13028.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.multipool import (backtrace_splits_jnp, col_jnp,
                                  final_combine_jnp, minplus_fold_jnp)

# the fold/splits outputs are per-variant (Rp, FOLD_LANES) blocks; only
# lane 0 of min_e and lanes < C of splits are meaningful (lane-width
# padding keeps the blocks TPU-tileable)
FOLD_LANES = 128

# physical VMEM of one v5e TensorCore is 128 MiB; leave room for the
# compiler's internal scratch
VMEM_CAP = 100 * 2 ** 20
VMEM_HEADROOM = 8 * 2 ** 20


def vmem_bytes(T: int, Kp: int, Rp: int, C: int, bk: int) -> int:
    """The kernel's VMEM footprint (see the module docstring)."""
    return 4 * (2 * (T + 1) * bk + (T + 1) * Kp
                + (2 + max(C - 2, 1)) * Rp * Kp + 4 * Rp * FOLD_LANES)


def _emit(fold_ref, splits_ref, min_e, splits, Rp: int) -> None:
    """Write the (Rp, 1) min-energy and the C (Rp, 1) split columns into
    the padded per-variant output blocks."""
    fold_ref[0] = jnp.broadcast_to(min_e, (Rp, FOLD_LANES))
    col = jax.lax.broadcasted_iota(jnp.int32, (Rp, FOLD_LANES), 1)
    out = jnp.full((Rp, FOLD_LANES), -1, jnp.int32)
    for c, s in enumerate(splits):
        out = jnp.where(col == c, s, out)
    splits_ref[0] = out


def _fused_kernel(t_ref, e_ref, rows_ref, stages_ref, fold_ref, splits_ref,
                  S, F, G, A, *, T1: int, K: int, bk: int, C: int, n: int,
                  P: int, Rp: int):
    v = pl.program_id(0)
    c = pl.program_id(1)
    i = pl.program_id(2)
    p = pl.program_id(3)
    off = p * bk
    # the previous panel of S (clamped at p == 0, where carry is +inf)
    prev_p = jnp.maximum(p - 1, 0)
    t_i = t_ref[v, c, i]
    e_i = e_ref[v, c, i]
    blk = stages_ref.at[0, 0, 0]               # (T1, bk) stage-i panel

    # seed this panel with the previous stage: the k=0 base pattern for
    # the first space, the S rolling buffer (stage i-1 at panels >= p,
    # not yet overwritten) afterwards
    @pl.when(i == 0)
    def _seed_base():
        col = jax.lax.broadcasted_iota(jnp.int32, (T1, bk), 1) + off
        blk[...] = jnp.where(col == 0, 0.0, float("inf")).astype(jnp.float32)

    @pl.when(i > 0)
    def _seed_prev():
        blk[...] = S[p]

    def body(t, _):
        row = blk[t, :]                        # prev stage: not yet written
        prev_t = jnp.maximum(t - t_i, 0)
        # dp_new[t, k] uses dp_new[t - t_i, k - 1]: rows < t are already
        # updated in place; the k-1 column of the first lane is the last
        # column of the previous panel, already stage i in S
        carry = jnp.where(p == 0, float("inf"),
                          S[prev_p, prev_t, bk - 1:])
        shifted = jnp.concatenate([carry, blk[prev_t, :-1]])
        take = jnp.where(t >= t_i, shifted + e_i, float("inf"))
        blk[t, :] = jnp.minimum(row, take)
        return 0

    jax.lax.fori_loop(0, T1, body, 0, unroll=False)

    S[p] = blk[...]

    # last space of the cluster: gather the consulted t-grid rows of the
    # cluster's final table, panel by panel
    @pl.when(i == n - 1)
    def _gather_rows():
        def g_body(r, _):
            G[p, pl.ds(r, 1), :] = blk[pl.ds(rows_ref[v, r], 1), :]
            return 0
        jax.lax.fori_loop(0, Rp, g_body, 0, unroll=False)

    last = (i == n - 1) & (p == P - 1)

    def table():                               # G's panels side by side
        return jnp.concatenate([G[q] for q in range(P)], axis=1)

    if C == 1:
        @pl.when(last)
        def _combine_single():
            min_e = col_jnp(table(), K)
            split = jnp.where(jnp.isfinite(min_e), jnp.int32(K),
                              jnp.int32(-1))
            _emit(fold_ref, splits_ref, min_e, [split], Rp)
        return

    @pl.when(last & (c == 0))
    def _fold_init():
        F[...] = table()

    if C > 2:
        @pl.when(last & (c > 0) & (c < C - 1))
        def _fold_middle():
            out, arg = minplus_fold_jnp(F[...], table(), K)
            F[...] = out
            A[c - 1] = arg

    @pl.when(last & (c == C - 1))
    def _fold_final():
        min_e, i_opt = final_combine_jnp(F[...], table(), K)
        splits = backtrace_splits_jnp([A[j] for j in range(C - 2)], i_opt,
                                      jnp.isfinite(min_e), K, C)
        _emit(fold_ref, splits_ref, min_e, splits, Rp)


@functools.partial(jax.jit, static_argnames=("T", "K", "bk", "interpret"))
def lut_pipeline_pallas(t_items: jnp.ndarray, e_items: jnp.ndarray,
                        rows: jnp.ndarray, *, T: int, K: int,
                        bk: int = 128, interpret: bool = False):
    """Fused DP + combine in one ``pallas_call`` (see module docstring).

    Same contract as :func:`repro.kernels.lut_pipeline.ref.lut_pipeline_ref`:
    ``t_items``/``e_items`` (V, C, n) inert-padded costs, ``rows`` (V, R)
    consulted tick rows; returns ``(stages, min_e, splits)`` with the
    k=0 base stage omitted.
    """
    V, C, n = t_items.shape
    R = rows.shape[1]
    T1 = T + 1
    if C > FOLD_LANES:
        raise ValueError(f"cluster count {C} exceeds the splits-output "
                         f"lane width {FOLD_LANES}")
    Kp = (K + 1) + ((-(K + 1)) % bk)
    P = Kp // bk
    Rp = R + ((-R) % 8)
    need = vmem_bytes(T, Kp, Rp, C, bk)
    if need > VMEM_CAP:
        raise ValueError(
            f"lut_pipeline needs {need} B of VMEM at T={T}, K={K}, bk={bk}, "
            f"C={C}, R={R} (4*(2*(T+1)*bk + (T+1)*Kp + "
            f"(2+max(C-2,1))*Rp*Kp + 4*Rp*{FOLD_LANES})), over the "
            f"{VMEM_CAP} B cap; lower the tick horizon or bk")
    rows_p = jnp.pad(rows, ((0, 0), (0, Rp - R)))

    kernel = functools.partial(_fused_kernel, T1=T1, K=K, bk=bk, C=C,
                               n=n, P=P, Rp=Rp)

    def smem(arr):
        return pl.BlockSpec(arr.shape,
                            lambda v, c, i, p: (0,) * arr.ndim,
                            memory_space=pltpu.SMEM)

    t_arr = t_items.astype(jnp.int32)
    e_arr = e_items.astype(jnp.float32)
    stages, fold, splits = pl.pallas_call(
        kernel,
        grid=(V, C, n, P),
        in_specs=[smem(t_arr), smem(e_arr), smem(rows_p)],
        out_specs=(
            pl.BlockSpec((1, 1, 1, T1, bk),
                         lambda v, c, i, p: (v, c, i, 0, p)),
            pl.BlockSpec((1, Rp, FOLD_LANES), lambda v, c, i, p: (v, 0, 0)),
            pl.BlockSpec((1, Rp, FOLD_LANES), lambda v, c, i, p: (v, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((V, C, n, T1, Kp), jnp.float32),
            jax.ShapeDtypeStruct((V, Rp, FOLD_LANES), jnp.float32),
            jax.ShapeDtypeStruct((V, Rp, FOLD_LANES), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((P, T1, bk), jnp.float32),            # S
            pltpu.VMEM((Rp, Kp), jnp.float32),               # F
            pltpu.VMEM((P, Rp, bk), jnp.float32),            # G
            pltpu.VMEM((max(C - 2, 1), Rp, Kp), jnp.int32),  # A
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need + VMEM_HEADROOM),
        interpret=interpret,
    )(t_arr, e_arr, rows_p)
    return stages[..., :K + 1], fold[:, :R, 0], splits[:, :R, :C]
