"""Coded-computation baselines the paper compares against (Sec. VI-B).

* PC   — polynomially coded regression [13]: worker i stores r coded
         matrices (one per group of G = ceil(n/r) data parts), computes the
         SUM of its r Gram-vector products, sends ONE message. The master
         recovers X^T X theta from any 2G - 1 workers by polynomial
         interpolation.
* PCMM — polynomially coded multi-message [17]: worker i stores r Lagrange-
         coded matrices (each mixing ALL n parts, evaluated at distinct
         points beta_{i,j}), computes them sequentially and sends each
         result immediately. The master recovers from any 2n - 1 received
         computations.

Unlike the paper's experiments (which *ignore* encode/decode cost), the full
codec is implemented: ``pc_encode/pc_decode`` and ``pcmm_encode/pcmm_decode``
really interpolate, so tests verify exact recovery, and the optional decode
timer in benchmarks can expose the cost the paper footnotes away.

Completion-time models (used in benchmarks, matching the paper's setup):

* PC completion   = (2*ceil(n/r)-1)-th order statistic of per-worker times
                    t_i = sum_j T1[i,j] + T2[i, last]        (eq. 51-52)
* PCMM completion = (2n-1)-th order statistic of ALL slot arrivals (eq. 56-57)
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import numpy as np

from . import montecarlo

__all__ = [
    "pc_threshold", "pcmm_threshold", "pc_encode", "pc_worker_compute",
    "pc_decode", "pcmm_encode", "pcmm_worker_compute", "pcmm_decode",
    "simulate_pc_completion", "simulate_pcmm_completion",
]


def pc_threshold(n: int, r: int) -> int:
    return 2 * math.ceil(n / r) - 1


def pcmm_threshold(n: int) -> int:
    return 2 * n - 1


def _lagrange_basis(points: np.ndarray, x: np.ndarray) -> np.ndarray:
    """L[m, t] = prod_{p != m} (x[t] - points[p]) / (points[m] - points[p])."""
    P = len(points)
    L = np.ones((P, len(np.atleast_1d(x))))
    x = np.atleast_1d(x).astype(np.float64)
    for m in range(P):
        for p in range(P):
            if p != m:
                L[m] *= (x - points[p]) / (points[m] - points[p])
    return L


# --------------------------------- PC ----------------------------------------

def _pc_groups(n: int, r: int) -> Tuple[np.ndarray, int]:
    """Partition task indices [n] into r groups of size G = ceil(n/r),
    padded with -1 (zero data)."""
    G = math.ceil(n / r)
    idx = np.full((r, G), -1, dtype=np.int64)
    flat = np.arange(n)
    for j in range(r):
        chunk = flat[j * G:(j + 1) * G]
        idx[j, :len(chunk)] = chunk
    return idx, G


def pc_encode(X_parts: np.ndarray, r: int, alphas: np.ndarray | None = None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Encode the n data parts for PC.

    X_parts: (n, d, b) — the n sub-matrices X_i (b = N/n columns each).
    Returns (Xt, alphas, group_idx): Xt[i, j] = p_j(alpha_i) where p_j is the
    degree-(G-1) polynomial through the parts of group j at points 1..G.
    Shapes: Xt (n, r, d, b).
    """
    n, d, b = X_parts.shape
    group_idx, G = _pc_groups(n, r)
    if alphas is None:
        alphas = np.arange(1, n + 1, dtype=np.float64)   # worker eval points
    pts = np.arange(1, G + 1, dtype=np.float64)          # interpolation nodes
    L = _lagrange_basis(pts, alphas)                     # (G, n)
    Xt = np.zeros((n, r, d, b))
    for j in range(r):
        for m in range(G):
            p = group_idx[j, m]
            if p >= 0:
                Xt[:, j] += L[m][:, None, None] * X_parts[p]
    return Xt, alphas, group_idx


def pc_worker_compute(Xt_i: np.ndarray, theta: np.ndarray) -> np.ndarray:
    """Worker i's single message: sum_j Xt[i,j] @ (Xt[i,j].T @ theta)."""
    return sum(Xij @ (Xij.T @ theta) for Xij in Xt_i)


def pc_decode(results: np.ndarray, alphas_rx: np.ndarray, n: int, r: int
              ) -> np.ndarray:
    """Interpolate phi(x) = sum_j p_j(x) p_j(x)^T theta (degree 2G-2) from
    >= 2G-1 worker results, then return sum_{m=1..G} phi(m) = X^T X theta.

    results: (w, d) rows phi(alpha_i) from w >= 2G-1 distinct workers.
    """
    G = math.ceil(n / r)
    need = 2 * G - 1
    if len(alphas_rx) < need:
        raise ValueError(f"PC needs {need} results, got {len(alphas_rx)}")
    A = np.vander(np.asarray(alphas_rx, np.float64), need, increasing=True)
    coef, *_ = np.linalg.lstsq(A, np.asarray(results, np.float64), rcond=None)
    pts = np.arange(1, G + 1, dtype=np.float64)
    V = np.vander(pts, need, increasing=True)            # (G, need)
    return (V @ coef).sum(axis=0)


# -------------------------------- PCMM ---------------------------------------

def pcmm_encode(X_parts: np.ndarray, r: int, betas: np.ndarray | None = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Lagrange-code all n parts; worker i's j-th matrix is the degree-(n-1)
    polynomial through X_1..X_n (at nodes 1..n) evaluated at beta[i, j].

    Returns (Xh, betas): Xh (n, r, d, b)."""
    n, d, b = X_parts.shape
    if betas is None:
        # Chebyshev points spanning the interpolation nodes [1, n]: well-
        # conditioned (evaluation at 1..n is interpolation, not extrapolation)
        m = n * r
        cheb = np.cos((2 * np.arange(1, m + 1) - 1) / (2 * m) * np.pi)
        betas = (0.5 * (1 + n) + 0.5 * (n - 0.5) * cheb).reshape(n, r)
    nodes = np.arange(1, n + 1, dtype=np.float64)
    L = _lagrange_basis(nodes, betas.reshape(-1))        # (n, n*r)
    Xh = np.einsum("mp,mdb->pdb", L, X_parts).reshape(n, r, d, b)
    return Xh, betas


def pcmm_worker_compute(Xh_ij: np.ndarray, theta: np.ndarray) -> np.ndarray:
    """One sequential message: Xh_ij @ (Xh_ij.T @ theta)."""
    return Xh_ij @ (Xh_ij.T @ theta)


def pcmm_decode(results: np.ndarray, betas_rx: np.ndarray, n: int
                ) -> np.ndarray:
    """Interpolate phi2(x) (degree 2n-2) from >= 2n-1 results, then return
    sum_{i=1..n} phi2(i) = X^T X theta.

    Uses a Chebyshev basis over the hull of {received points} ∪ {1..n}: the
    encode points are Chebyshev-distributed, so the least-squares system is
    well-conditioned even at degree 2n-2 (a monomial Vandermonde is
    numerically hopeless beyond n ~ 6 — a real cost of PCMM the paper does
    not discuss)."""
    need = 2 * n - 1
    if len(betas_rx) < need:
        raise ValueError(f"PCMM needs {need} results, got {len(betas_rx)}")
    x = np.asarray(betas_rx, np.float64)
    nodes = np.arange(1, n + 1, dtype=np.float64)
    lo = min(x.min(), nodes.min()) - 1e-9
    hi = max(x.max(), nodes.max()) + 1e-9
    tx = (2 * x - (lo + hi)) / (hi - lo)
    A = np.polynomial.chebyshev.chebvander(tx, need - 1)
    coef, *_ = np.linalg.lstsq(A, np.asarray(results, np.float64),
                               rcond=None)
    tn = (2 * nodes - (lo + hi)) / (hi - lo)
    V = np.polynomial.chebyshev.chebvander(tn, need - 1)
    return (V @ coef).sum(axis=0)


# --------------------- completion-time simulation ----------------------------
# Backed by the fused sweep engine (montecarlo.py): per-trial subkeys mean
# the draws are the common random numbers shared with the uncoded schemes
# when evaluated inside one sweep, and a rank count replaces the full sort.

def simulate_pc_completion(model, n: int, r: int, *, trials: int = 10000,
                           seed: int = 0, chunk: int | None = None
                           ) -> jax.Array:
    """eq. (51)-(52): worker i's single message lands at
    sum_j T1[i, j] + T2[i, -1]; completion = (2*ceil(n/r)-1)-th order stat."""
    return montecarlo.completion_samples(
        montecarlo.pc_spec(r), model, n, trials=trials, seed=seed,
        chunk=chunk)


def simulate_pcmm_completion(model, n: int, r: int, *, trials: int = 10000,
                             seed: int = 0, chunk: int | None = None
                             ) -> jax.Array:
    """eq. (56)-(57): all n*r slot arrivals; completion = (2n-1)-th order
    statistic (requires n*r >= 2n-1, i.e. r >= 2 as in the paper)."""
    if n * r < pcmm_threshold(n):
        raise ValueError(f"PCMM infeasible: n*r={n*r} < 2n-1={2*n-1}")
    return montecarlo.completion_samples(
        montecarlo.pcmm_spec(r), model, n, trials=trials, seed=seed,
        chunk=chunk)
