"""Pallas kernel for the adaptive greedy row assignment (paper Sec. V +
Egger et al., arXiv:2304.08589), batched over Monte-Carlo trials.

The greedy is a sequential pick loop — n pickers (fastest worker first),
each taking the row with the least discounted task coverage — that the
rounds engine runs per trial per round.  The pick loop is inherently
sequential, but with the static coverage-weight matrix
``W[p, t] = sum_j gamma**j * [C[p, j] == t]`` each step collapses to
dense lane-parallel ops over a block of trials:

  scores  = cov @ W^T                 (fixed-order sum over tasks)
  p       = argmin over rows          (min + iota trick, ties -> lowest)
  cov    += W[p] * (1 / e_pick)       (one-hot select of row p)

so the whole O(n^2 * r) scan becomes n steps of lane-parallel vector
ops on a (block, n) trial block held in VMEM — no gathers, no per-trial
control flow.

``greedy_assign_pallas`` is the raw kernel (grid over trial blocks,
interpret-mode fallback on CPU); ``repro.kernels.ref.greedy_assign_ref``
is the pure-jnp oracle twin; ``repro.kernels.ops.greedy_assign`` the
jitted public wrapper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .gram_matvec import resolve_interpret

DEFAULT_BLOCK_TRIALS = 128


def coverage_scores(cov: jax.Array, wt_rows) -> jax.Array:
    """Greedy row scores ``cov @ W.T`` as one fixed-order sum over tasks.

    ``cov`` (B, n) per-task coverage, ``wt_rows`` the n rows of ``W.T``,
    each (1, n).  The sum runs t = 0, 1, ..., n-1 in that order on the
    vector unit.  A matmul would leave the order, and on a TPU the
    precision, to the backend, and the greedy's argmin then breaks ties
    between rows that are equal in exact arithmetic differently in the
    Pallas kernel and in its scan twin ``ref.greedy_assign_ref``."""
    acc = cov[:, 0:1] * wt_rows[0]
    for t in range(1, len(wt_rows)):
        acc = acc + cov[:, t:t + 1] * wt_rows[t]
    return acc


def _greedy_kernel(w_ref, wt_ref, order_ref, inv_ref, need_ref, out_ref):
    """One (block, n) trial block: run all n picks to completion.

    Refs: ``w_ref`` (n, n) f32 coverage weights and ``wt_ref`` their
    transpose; ``order_ref`` (bt, n) i32 pickers fastest-first;
    ``inv_ref`` (bt, n) f32 reciprocals of the sorted delay estimates;
    ``need_ref`` (bt, n) f32 reissue priorities (all-zero =
    none); ``out_ref`` (bt, n) i32 worker-of-row.

    Mosaic lowers no ``dynamic_slice`` and no boolean loop carry, so the
    pick-``t`` columns are read through a lane mask and ``taken`` is a
    0/1 float.  Scores use the fixed-order sum of ``coverage_scores``
    (no MXU dot), the same one the scan twin uses, so scores equal in
    exact arithmetic compare equal here and there on every backend.  The
    reciprocals are taken outside the kernel, as the twin takes them, so
    that the kernel only multiplies (rounded alike everywhere) and never
    divides (which Mosaic and XLA may lower differently)."""
    n = w_ref.shape[0]
    wrows = [w_ref[p:p + 1, :] for p in range(n)]
    wtrows = [wt_ref[t:t + 1, :] for t in range(n)]
    order = order_ref[...]
    inv_e = inv_ref[...]
    need = need_ref[...]
    bt = order.shape[0]
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bt, n), 1)

    def pick(t, carry):
        cov, taken, wout = carry
        scores = jnp.where(taken > 0, big, coverage_scores(cov, wtrows))
        pref = jnp.where((need > 0) & (taken == 0), scores, big)
        has = jnp.min(pref, axis=-1, keepdims=True) < big
        sel = jnp.where(has, pref, scores)
        m = jnp.min(sel, axis=-1, keepdims=True)
        p = jnp.min(jnp.where(sel == m, lanes, n), axis=-1, keepdims=True)
        hit = lanes == p                                   # ties -> lowest
        col = lanes == t
        wid = jnp.sum(jnp.where(col, order, 0), axis=-1, keepdims=True)
        inv_t = jnp.sum(jnp.where(col, inv_e, 0.0), axis=-1, keepdims=True)
        wout = jnp.where(hit, wid, wout)
        taken = jnp.where(hit, 1.0, taken)
        w_p = wrows[0]
        for q in range(1, n):                             # exact select
            w_p = jnp.where(p == q, wrows[q], w_p)
        cov = cov + w_p * inv_t
        return cov, taken, wout

    init = (jnp.zeros((bt, n), jnp.float32), jnp.zeros((bt, n), jnp.float32),
            jnp.zeros((bt, n), jnp.int32))
    _, _, wout = jax.lax.fori_loop(0, n, pick, init)
    out_ref[...] = wout


def greedy_assign_pallas(W: jax.Array, order: jax.Array, epick: jax.Array,
                         need_row: jax.Array | None = None, *,
                         block_trials: int = DEFAULT_BLOCK_TRIALS,
                         interpret: bool | None = None) -> jax.Array:
    """Batched greedy row assignment.  ``W`` (n, n) f32 static coverage
    weights, ``order``/``epick``/``need_row`` (B, n) per-trial pick data
    (see ``repro.kernels.ref.greedy_assign_ref`` for semantics) ->
    ``worker_of_row`` (B, n) int32.  ``interpret`` defaults to
    backend-aware: compiled on TPU/GPU, interpreted on CPU."""
    interpret = resolve_interpret(interpret)
    W = W.astype(jnp.float32)
    epick = epick.astype(jnp.float32)
    B, n = order.shape
    if need_row is None:
        need_row = jnp.zeros((B, n), jnp.float32)
    bt = min(block_trials, B)
    pad = (-B) % bt
    if pad:
        # edge-pad: padded trials recompute the last real trial's picks and
        # are sliced off — rows are independent, so real lanes are exact.
        order = jnp.pad(order, ((0, pad), (0, 0)), mode="edge")
        epick = jnp.pad(epick, ((0, pad), (0, 0)), mode="edge")
        need_row = jnp.pad(need_row, ((0, pad), (0, 0)), mode="edge")
    Bp = B + pad

    out = pl.pallas_call(
        _greedy_kernel,
        grid=(Bp // bt,),
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0)),
                  pl.BlockSpec((n, n), lambda i: (0, 0)),
                  pl.BlockSpec((bt, n), lambda i: (i, 0)),
                  pl.BlockSpec((bt, n), lambda i: (i, 0)),
                  pl.BlockSpec((bt, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bt, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, n), jnp.int32),
        interpret=interpret,
        name="greedy_assign",
    )(W, W.T, order.astype(jnp.int32), 1.0 / epick,
      need_row.astype(jnp.float32))

    return out[:B]
