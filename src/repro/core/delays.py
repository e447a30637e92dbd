"""Statistical models for per-task computation (T^(1)) and per-result
communication (T^(2)) delays (paper Sec. II and Sec. VI-C).

Every model samples a pair of arrays ``(T1, T2)`` of shape
``(trials, n_workers, n_slots)``:

  * ``T1[t, i, j]`` — computation delay of the j-th *slot* at worker i
    (the slot's task identity comes from the TO matrix; delay statistics are
    order-independent, paper Remark 6).
  * ``T2[t, i, j]`` — communication delay of that slot's result.

Delays are independent across workers. Within a worker they may be dependent
(the paper's general model); ``rho`` adds an equicorrelated worker-level
random effect so tasks at the same worker share a slow/fast tendency.

The paper's EC2 calibration (Fig. 3): truncated Gaussians,
  scenario 1: mu1=1e-4, mu2=5e-4, a1=3e-5, s1=1e-4(*), a2=2e-4, s2=2e-4
(*) the paper's "alpha E beta" notation means alpha*10^-beta: a1=3E5=3e-5,
    sigma1=1E4=1e-4, a2=2E4=2e-4, sigma2=2E4=2e-4, mu1=1E4=1e-4, mu2=5E4=5e-4.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "DelayModel", "TruncatedGaussianDelays", "ShiftedExponentialDelays",
    "BimodalStragglerDelays", "EmpiricalDelays", "scenario1", "scenario2",
    "ec2_like",
]

Array = jax.Array


def _truncnorm(key, shape, mu, sigma, lo, hi):
    """Sample a truncated normal on [lo, hi] elementwise (mu/sigma/lo/hi
    broadcastable to ``shape``)."""
    a = (lo - mu) / sigma
    b = (hi - mu) / sigma
    z = jax.random.truncated_normal(key, a, b, shape)
    return mu + sigma * z


@dataclasses.dataclass(frozen=True)
class DelayModel:
    """Base class. Subclasses implement ``_sample(key, trials, n, r)``
    returning (T1, T2) with shape (trials, n, r)."""

    def sample(self, key: Array, trials: int, n: int, r: int
               ) -> Tuple[Array, Array]:
        T1, T2 = self._sample(key, trials, n, r)
        assert T1.shape == (trials, n, r) and T2.shape == (trials, n, r)
        return T1, T2

    def _sample(self, key, trials, n, r):  # pragma: no cover - abstract
        raise NotImplementedError

    def as_process(self):
        """This model as a round-stateful ``DelayProcess`` (the
        zero-correlation special case; see ``repro.core.cluster``)."""
        from .cluster import IIDProcess
        return IIDProcess(self)


@dataclasses.dataclass(frozen=True)
class TruncatedGaussianDelays(DelayModel):
    """Paper Sec. VI-C (eq. 66): per-worker truncated Gaussian delays on
    [mu - a, mu + b]. ``mu1/mu2`` may be scalars or length-n vectors
    (scenario 2 uses per-worker means). ``rho`` in [0, 1) makes slots at the
    same worker positively correlated via a shared worker effect."""
    mu1: tuple | float = 1e-4
    sigma1: float = 1e-4
    a1: float = 3e-5
    mu2: tuple | float = 5e-4
    sigma2: float = 2e-4
    a2: float = 2e-4
    b1: float | None = None  # defaults to a1 (symmetric, as in the paper)
    b2: float | None = None
    rho: float = 0.0

    def _one(self, key, trials, n, r, mu, sigma, a, b):
        mu = jnp.asarray(mu, jnp.float32)
        mu = jnp.broadcast_to(mu, (n,))[None, :, None]  # (1, n, 1)
        b = a if b is None else b
        lo, hi = mu - a, mu + b
        if self.rho > 0.0:
            kw, ks = jax.random.split(key)
            # worker-level effect + slot-level effect, equicorrelated rho.
            w = _truncnorm(kw, (trials, n, 1), 0.0, 1.0, -3.0, 3.0)
            e = _truncnorm(ks, (trials, n, r), 0.0, 1.0, -3.0, 3.0)
            z = np.sqrt(self.rho) * w + np.sqrt(1 - self.rho) * e
            t = mu + sigma * z
            return jnp.clip(t, lo, hi)
        # The standardized bounds are the same for every worker, so they
        # stay scalars.  Per-worker bounds would put erf of a constant
        # vector in the graph, which XLA folds on the host for some loop
        # shapes and not others: draws then depended on the chunking.
        z = jax.random.truncated_normal(key, -a / sigma, b / sigma,
                                        (trials, n, r))
        return mu + sigma * z

    def _sample(self, key, trials, n, r):
        k1, k2 = jax.random.split(key)
        T1 = self._one(k1, trials, n, r, self.mu1, self.sigma1, self.a1, self.b1)
        T2 = self._one(k2, trials, n, r, self.mu2, self.sigma2, self.a2, self.b2)
        return T1, T2


@dataclasses.dataclass(frozen=True)
class ShiftedExponentialDelays(DelayModel):
    """Classic straggler model (Lee et al. [3]): T = shift + Exp(rate).
    Scale-parameterized: T1 ~ s1 + Exp(mean=m1), per slot."""
    shift1: float = 1e-4
    mean1: float = 5e-5
    shift2: float = 2e-4
    mean2: float = 1e-4

    def _sample(self, key, trials, n, r):
        k1, k2 = jax.random.split(key)
        T1 = self.shift1 + self.mean1 * jax.random.exponential(k1, (trials, n, r))
        T2 = self.shift2 + self.mean2 * jax.random.exponential(k2, (trials, n, r))
        return T1, T2


@dataclasses.dataclass(frozen=True)
class BimodalStragglerDelays(DelayModel):
    """Persistent-straggler model: with prob ``p_straggle`` a worker's entire
    row is slowed by factor ``slow`` for the round (models a busy neighbor
    VM). Base delays are truncated Gaussian."""
    base: TruncatedGaussianDelays = TruncatedGaussianDelays()
    p_straggle: float = 0.2
    slow: float = 5.0

    def _sample(self, key, trials, n, r):
        kb, ks = jax.random.split(key)
        T1, T2 = self.base._sample(kb, trials, n, r)
        mask = jax.random.bernoulli(ks, self.p_straggle, (trials, n, 1))
        f = jnp.where(mask, self.slow, 1.0)
        return T1 * f, T2 * f


@dataclasses.dataclass(frozen=True)
class EmpiricalDelays(DelayModel):
    """Bootstrap-resample measured per-task delays. ``samples1/2`` are
    arrays of shape (n_measured, n) — rows = measured rounds. On a real
    cluster these come from timestamp logs (see launch/train.py --log-delays).
    """
    samples1: tuple = ()
    samples2: tuple = ()

    def _sample(self, key, trials, n, r):
        s1 = jnp.asarray(self.samples1, jnp.float32)
        s2 = jnp.asarray(self.samples2, jnp.float32)
        if s1.ndim != 2 or s1.shape[1] != n:
            raise ValueError(f"samples1 must be (rounds, n={n}); got {s1.shape}")
        k1, k2 = jax.random.split(key)
        i1 = jax.random.randint(k1, (trials, n, r), 0, s1.shape[0])
        i2 = jax.random.randint(k2, (trials, n, r), 0, s2.shape[0])
        w = jnp.arange(n)[None, :, None]
        return s1[i1, w], s2[i2, w]


# ---- Paper's two numerical scenarios (Sec. VI-C, Fig. 4) -------------------

def scenario1() -> TruncatedGaussianDelays:
    """mu1 = 1e-4, mu2 = 5e-4 for all workers."""
    return TruncatedGaussianDelays(mu1=1e-4, mu2=5e-4)


def scenario2(n: int, seed: int = 0) -> TruncatedGaussianDelays:
    """Per-worker means: mu1 a random permutation of {1e-4, 4/3e-4, ...,
    (2+n)/3 e-4}; mu2 of {5e-4, 5.5e-4, ..., (9+n)/2 e-4}."""
    rng = np.random.default_rng(seed)
    mu1 = (2 + np.arange(1, n + 1)) / 3 * 1e-4
    mu2 = (9 + np.arange(1, n + 1)) / 2 * 1e-4
    return TruncatedGaussianDelays(mu1=tuple(rng.permutation(mu1).tolist()),
                                   mu2=tuple(rng.permutation(mu2).tolist()))


def ec2_like(n: int, seed: int = 0, comm_over_comp: float = 5.0
             ) -> TruncatedGaussianDelays:
    """Fig. 3-style: communication dominates computation by ~comm_over_comp;
    mild heterogeneity across workers."""
    rng = np.random.default_rng(seed)
    mu1 = 1e-4 * (1.0 + 0.3 * rng.random(n))
    mu2 = comm_over_comp * 1e-4 * (1.0 + 0.3 * rng.random(n))
    return TruncatedGaussianDelays(mu1=tuple(mu1.tolist()), mu2=tuple(mu2.tolist()))
