"""``repro.core.multipool`` - K-cluster placement combine (DESIGN.md SS.7).

Algorithm 2 of the paper combines exactly two clusters by scanning
``k_hp + k_lp = K``. :func:`combine_many` generalizes it to any cluster
count ``C`` as a min-plus (tropical) convolution fold over the
per-cluster energy tables ``E_c[r, k]`` (min energy of placing ``k``
weight groups in cluster ``c`` at row ``r`` - a time-tick row on the DP
path, a t-grid row on the closed-form path):

    (A (+) E)[r, k] = min_i A[r, i] + E[r, k - i]

Each fold keeps its argmin-``i`` trace, so the optimal per-cluster
split is recovered by backtracing from ``k = K`` through the stored
prefix counts. The final fold is evaluated only at ``k = K`` (the full
weight count), which for ``C == 2`` degenerates to exactly the pairwise
Algorithm-2 scan - the same float additions in the same order and the
same first-minimum ``argmin`` - keeping every pre-existing 1- and
2-cluster LUT byte-identical through the refactor (asserted by the
golden-digest regression suite in tests/test_multipool.py).

Complexity: one full fold is O(R * K^2) time / O(R * K) memory, and a
C-cluster combine is ``C - 2`` full folds plus the O(R * K) final
combine - linear in the cluster count, quadratic in the group count
like Algorithm 2 itself. The fold is row-local (row ``r`` of the output
depends only on row ``r`` of the inputs), so callers may slice tables
to the consulted rows *before* combining without changing any byte of
the result - `build_lut(method="dp")` exploits this to fold only the
grid's tick rows instead of all ``T + 1``.

Dtype note: inputs are combined in their own dtype (float32 DP tables,
float64 closed-form tables) - no up-cast, so the K=2 degenerate case
reproduces the historic pairwise arithmetic bit-for-bit.

Two implementations of the same fold live here:

  * the numpy pair (:func:`minplus_fold` / :func:`combine_many`) - the
    historic host path, still the float64 closed-form combiner (jax
    runs float32 by default, so up-lowering it would break the
    closed-form byte contract);
  * the jax pair (:func:`minplus_fold_jnp` / :func:`combine_rows_jnp`)
    - the device path behind the fused LUT pipeline
    (:mod:`repro.kernels.lut_pipeline`). ``minplus_fold_jnp`` is written
    against pure jnp/lax primitives that lower inside a Pallas kernel
    body, so the fused kernel and the jitted ref backend literally
    share this function. Candidate generation order, strict-< updates
    and first-minimum argmin are identical to the numpy pair, so both
    produce the same float bits and the same integer splits on the
    same float32 tables (asserted by tests/test_lut_pipeline.py).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

INF = float("inf")


def minplus_fold(a: np.ndarray, e: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """One min-plus convolution step with its argmin trace.

    Args:
      a: (R, K+1) prefix table - min energy of placing ``i`` groups in
         the clusters folded so far.
      e: (R, K+1) next cluster's table.

    Returns:
      out: (R, K+1) folded table ``out[r, k] = min_i a[r, i] + e[r, k-i]``.
      arg: (R, K+1) int64 argmin prefix count ``i`` (ties -> smallest
           ``i``, matching ``np.argmin``'s first-minimum rule).
    """
    if a.shape != e.shape:
        raise ValueError(f"table shapes differ: {a.shape} vs {e.shape}")
    R, K1 = a.shape
    out = np.full((R, K1), INF, dtype=a.dtype)
    arg = np.zeros((R, K1), dtype=np.int64)
    for i in range(K1):
        cand = a[:, i:i + 1] + e[:, :K1 - i]
        tail = out[:, i:]
        take = cand < tail                 # strict: first minimum wins
        tail[take] = cand[take]
        arg[:, i:][take] = i
    return out, arg


def combine_many(tables: Sequence[np.ndarray]
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Min-plus fold of ``C`` per-cluster tables with split backtrace.

    Args:
      tables: ``C`` arrays, each (R, K+1); ``tables[c][r, k]`` is the
        min energy of placing exactly ``k`` weight groups in cluster
        ``c`` at row ``r`` (+inf where infeasible).

    Returns:
      min_e:  (R,) minimum total energy of placing all ``K`` groups.
      splits: (R, C) int64 per-cluster group counts at the optimum,
        summing to ``K`` on every feasible row; all ``-1`` on
        infeasible rows.
    """
    tables = [np.asarray(t) for t in tables]
    if not tables:
        raise ValueError("combine_many needs at least one cluster table")
    if tables[0].ndim != 2:
        raise ValueError(f"cluster 0: table must be 2-D (R, K+1), got "
                         f"shape {tables[0].shape}")
    R, K1 = tables[0].shape
    for c, t in enumerate(tables[1:], start=1):
        if t.shape != (R, K1):
            raise ValueError(
                f"cluster {c}: table shape {t.shape} disagrees with the "
                f"fold accumulator {(R, K1)} (cluster 0 sets the shared "
                f"(R, K+1) shape; the fold is row-aligned, so every "
                f"cluster must be sliced to the same rows)")
    C = len(tables)
    K = K1 - 1
    rows = np.arange(R)

    if C == 1:
        min_e = tables[0][:, K]
        splits = np.where(np.isfinite(min_e)[:, None], K,
                          -1).astype(np.int64)
        return min_e, splits

    # fold all but the last cluster into full-k prefix tables
    args: List[np.ndarray] = []
    F = tables[0]
    for c in range(1, C - 1):
        F, A = minplus_fold(F, tables[c])
        args.append(A)

    # final combine, evaluated only at k = K; for C == 2 this IS the
    # pairwise Algorithm-2 scan (same additions, same first-min argmin)
    cand = F + tables[C - 1][:, ::-1]      # cand[r, i] = F[r,i] + E[r,K-i]
    i_opt = np.argmin(cand, axis=1)
    min_e = cand[rows, i_opt]
    feasible = np.isfinite(min_e)

    splits = np.full((R, C), -1, dtype=np.int64)
    splits[feasible, C - 1] = K - i_opt[feasible]
    k = np.where(feasible, i_opt, 0)       # groups left in clusters 0..C-2
    for c in range(C - 2, 0, -1):
        i_prev = args[c - 1][rows, k]
        splits[feasible, c] = (k - i_prev)[feasible]
        k = np.where(feasible, i_prev, 0)
    splits[feasible, 0] = k[feasible]
    return min_e, splits


# ---------------------------------------------------------------------------
# jax twin of the fold - shared by the fused LUT pipeline's ref backend
# (under jit) and its Pallas kernel body. Every body is written against
# primitives Mosaic lowers: a column at a traced index is a one-hot
# reduction (exact: x + 0 and min(x, inf) change no bit), a lane shift
# is ``pltpu.roll`` (jnp.roll under plain jit), and values stay 2-D,
# columns as (R, 1). Tables may carry lane padding past column K (the
# kernel pads to a lane multiple); no result at k <= K reads a padded
# column. Lazy jax import keeps the numpy path numpy-only.
# ---------------------------------------------------------------------------


def col_jnp(x, i):
    """Column ``i`` of the float (R, W) table ``x`` as (R, 1), without a
    dynamic slice (the lane index ``i`` may be traced)."""
    import jax
    import jax.numpy as jnp

    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.min(jnp.where(lane == i, x, float("inf")), axis=1,
                   keepdims=True)


def minplus_fold_jnp(a, e, K: Optional[int] = None):
    """jax :func:`minplus_fold`: same candidates, same order, same bits.

    Iterates the prefix count ``i`` ascending with a strict ``<`` update
    exactly like the numpy loop, so on equal inputs the returned values
    are bit-identical and the argmin trace picks the same (first)
    minimum. ``e`` is shifted by the traced ``i`` with a lane rotation
    whose wrapped-around columns ``k < i`` are masked to +inf.

    ``a``/``e`` are (R, W) with ``W >= K + 1`` (``K`` defaults to
    ``W - 1``); columns past ``K`` are lane padding, never read for a
    column ``<= K`` of the result. Returns ``(out, arg)`` (R, W) with
    ``arg`` int32 (the numpy twin returns int64; both hold prefix counts
    ``<= K``).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    R, W = a.shape
    K = W - 1 if K is None else K
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)

    def body(i, carry):
        out, arg = carry
        # g_shift[r, k] = e[r, k - i] for k >= i, else inf
        g_shift = jnp.where(lane >= i, pltpu.roll(e, i, 1), float("inf"))
        cand = col_jnp(a, i) + g_shift
        take = cand < out                  # strict: first minimum wins
        return (jnp.where(take, cand, out),
                jnp.where(take, i, arg))

    out0 = jnp.full((R, W), float("inf"), a.dtype)
    arg0 = jnp.zeros((R, W), jnp.int32)
    return jax.lax.fori_loop(0, K + 1, body, (out0, arg0))


def final_combine_jnp(F, E, K: int):
    """The fold's last step, evaluated at ``k = K`` only:
    ``min_i F[r, i] + E[r, K - i]`` with its first-minimum ``i``.

    Returns ``(min_e, i_opt)``, both (R, 1). Scans ``i`` ascending with
    a strict ``<`` update, so ``min_e`` holds the same bits and ``i_opt``
    the same index as ``min``/``argmin`` over the reversed-table sum of
    the numpy fold (an all-inf row keeps ``i_opt = 0``, as argmin does).
    """
    import jax
    import jax.numpy as jnp

    R = F.shape[0]

    def body(i, carry):
        best, arg = carry
        cand = col_jnp(F, i) + col_jnp(E, K - i)
        take = cand < best
        return jnp.where(take, cand, best), jnp.where(take, i, arg)

    return jax.lax.fori_loop(
        0, K + 1, body, (jnp.full((R, 1), float("inf"), F.dtype),
                         jnp.zeros((R, 1), jnp.int32)))


def backtrace_splits_jnp(args, i_opt, feasible, K: int, C: int):
    """Split recovery from fold argmin traces (jax).

    Args:
      args: list of ``C - 2`` (R, W) int32 argmin traces (the middle
        folds), possibly empty.
      i_opt: (R, 1) int32 - argmin prefix count of the final combine.
      feasible: (R, 1) bool.

    Returns a list of ``C`` (R, 1) int32 per-cluster counts; ``-1`` on
    infeasible rows. The lookup ``args[c][r, k[r]]`` is a one-hot
    reduction (no gather op).
    """
    import jax
    import jax.numpy as jnp

    k = i_opt
    cols = {C - 1: K - k}
    for c in range(C - 2, 0, -1):
        a_c = args[c - 1]
        lane = jax.lax.broadcasted_iota(jnp.int32, a_c.shape, 1)
        i_prev = jnp.sum(jnp.where(lane == k, a_c, 0), axis=1,
                         keepdims=True)
        cols[c] = k - i_prev
        k = i_prev
    cols[0] = k
    return [jnp.where(feasible, cols[c], -1) for c in range(C)]


def combine_rows_jnp(tables):
    """jax :func:`combine_many` over stacked tables ``(C, R, K+1)``.

    Same fold order, final-combine candidates and first-minimum argmin
    as the numpy fold, so the returned ``min_e`` bits and integer
    ``splits`` match :func:`combine_many` exactly on equal float32
    inputs. This is the combine the fused LUT pipeline's ref backend
    jits; the Pallas kernel calls the same :func:`minplus_fold_jnp` /
    :func:`final_combine_jnp` / :func:`backtrace_splits_jnp` in-kernel.
    """
    import jax.numpy as jnp

    C, R, K1 = tables.shape
    K = K1 - 1
    if C == 1:
        min_e = tables[0, :, K]
        feasible = jnp.isfinite(min_e)
        splits = jnp.where(feasible[:, None], jnp.int32(K),
                           jnp.int32(-1)).reshape(R, 1)
        return min_e, splits

    args = []
    F = tables[0]
    for c in range(1, C - 1):
        F, A = minplus_fold_jnp(F, tables[c])
        args.append(A)

    min_e, i_opt = final_combine_jnp(F, tables[C - 1], K)
    splits = backtrace_splits_jnp(args, i_opt, jnp.isfinite(min_e), K, C)
    return min_e[:, 0], jnp.concatenate(splits, axis=1)
