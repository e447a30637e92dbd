"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .greedy_assign import coverage_scores

__all__ = ["gram_matvec_ref", "swa_attention_ref", "greedy_assign_ref"]


def gram_matvec_ref(X: jax.Array, theta: jax.Array) -> jax.Array:
    """The paper's per-task computation h(X_i) = X_i X_i^T theta,
    X (d, b), theta (d,) -> (d,). Computed as X @ (X^T @ theta) — never
    materializing the (d, d) Gram matrix."""
    u = jnp.einsum("db,d->b", X.astype(jnp.float32),
                   theta.astype(jnp.float32))
    return jnp.einsum("db,b->d", X.astype(jnp.float32), u).astype(X.dtype)


def swa_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      window: int) -> jax.Array:
    """Causal sliding-window attention. q/k/v (T, H, dh) -> (T, H, dh).
    Position t attends to positions (t-window, t]."""
    T, H, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(T)[:, None]
    kpos = jnp.arange(T)[None, :]
    ok = (kpos <= qpos) & (kpos > qpos - window)
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def greedy_assign_ref(W: jax.Array, order: jax.Array, epick: jax.Array,
                      need_row: jax.Array | None = None) -> jax.Array:
    """Greedy row-assignment pick loop (oracle twin of the
    ``greedy_assign`` Pallas kernel; shared math with
    ``repro.core.scheduling.greedy_row_assignment_batch``).

    ``W`` is the static (n, n) float32 coverage-weight matrix of a TO
    matrix ``C``: ``W[p, t] = sum_j gamma**j * [C[p, j] == t]`` over the
    active slots of row ``p`` — so a row's greedy score is the single
    matvec ``cov @ W[p]`` and picking row ``p`` adds ``W[p] * (1 / e)`` to the
    per-task coverage.  ``order`` (B, n) int32 lists each trial's pickers
    fastest-first; ``epick`` (B, n) float32 the matching sorted delay
    estimates (pre-clamped away from zero); ``need_row`` (B, n), when
    given, marks rows holding backlogged tasks — while any un-taken row is
    needed, the argmin runs over those rows only (reissue priority).

    Returns ``worker_of_row`` (B, n) int32.  Ties break to the lowest row
    index (argmin semantics), matching the per-trial scan this replaces.
    """
    B, n = order.shape
    W = W.astype(jnp.float32)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    lanes = jnp.arange(n)[None, :]
    wt_rows = [W.T[t:t + 1] for t in range(n)]
    inv_e = 1.0 / epick.astype(jnp.float32)

    def pick(carry, t):
        cov, taken, wout = carry
        scores = jnp.where(taken, big, coverage_scores(cov, wt_rows))
        if need_row is None:
            sel = scores
        else:
            pref = jnp.where((need_row > 0) & ~taken, scores, big)
            has = jnp.min(pref, axis=-1, keepdims=True) < big
            sel = jnp.where(has, pref, scores)
        p = jnp.argmin(sel, axis=-1)                 # ties -> lowest row
        hit = lanes == p[:, None]
        wout = jnp.where(hit, order[:, t][:, None], wout)
        taken = taken | hit
        cov = cov + jnp.take(W, p, axis=0) * inv_e[:, t][:, None]
        return (cov, taken, wout), None

    init = (jnp.zeros((B, n), jnp.float32), jnp.zeros((B, n), bool),
            jnp.zeros((B, n), jnp.int32))
    (_, _, wout), _ = jax.lax.scan(pick, init, jnp.arange(n))
    return wout
