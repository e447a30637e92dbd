"""Plain Monte-Carlo of one scheduling round (arXiv:1810.09992 Sec. II-V).

An independent reference for the engine's single-round results: it
imports nothing of the program and draws its own delays.

    n workers; worker i runs the tasks of row i of the TO matrix C in
    order.  Slot j of worker i lands at
        s[i, j] = T1[i, 0] + ... + T1[i, j] + T2[i, j]          (eq. 1)
    with T1 ~ N(mu1, sigma1^2) truncated to mu1 +- a1 and
    T2 ~ N(mu2, sigma2^2) truncated to mu2 +- a2 (Sec. VI-C, eq. 66).

    A worker sends its r results in m messages (Sec. V-C): consecutive
    groups as even as possible, the earlier ones one larger.  A result
    arrives with its message, at the message's last slot, and the l-th
    message (from 0) lands (l + 1) * eps late.  By default every slot is
    its own message (eq. 1), and pc sends one.

    uncoded (cs / ss / ra): a task arrives with its first copy; the round
        closes at the k-th distinct task;
    lb:   the oracle bound (eq. 46), the k-th of all n*r result arrivals;
    pc:   polynomial codes, one message a worker at s[i, r-1]; decodable
          at the (2 ceil(n/r) - 1)-th (eqs. 51-52);
    pcmm: polynomial codes; decodable at the (2n - 1)-th partial result
          (eqs. 56-57).

Everything is computed in ``dtype`` (float32 for the reference; the
control computes it in bfloat16).  Per-trial statistics are summed in
float64 on the host.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def cyclic(n: int, r: int) -> np.ndarray:
    """CS (eq. 21): C[i, j] = (i + j) mod n."""
    return (np.arange(n)[:, None] + np.arange(r)[None, :]) % n


def staircase(n: int, r: int) -> np.ndarray:
    """SS (eq. 29): even rows ascend the ring, odd rows descend."""
    i = np.arange(n)[:, None]
    sign = np.where(i % 2 == 0, 1, -1)
    return (i + sign * np.arange(r)[None, :]) % n


def random_assignment(n: int, seed: int) -> np.ndarray:
    """RA [18]: r = n, each row an independent uniform permutation drawn
    from ``numpy.random.default_rng(seed)`` row after row."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(n) for _ in range(n)])


def to_matrix(scheme: dict, n: int) -> np.ndarray:
    fam, r = scheme["family"], int(scheme["r"])
    if fam == "cs":
        return cyclic(n, r)
    if fam == "ss":
        return staircase(n, r)
    if fam == "ra":
        if r != n:
            raise ValueError(f"ra needs r == n, got r={r}")
        return random_assignment(n, int(scheme.get("seed", 0)))
    raise ValueError(f"{fam} has no TO matrix")


def message_layout(r: int, m: int):
    """(closing slot, message index) of each of r slots sent in m
    messages."""
    sizes = [len(g) for g in np.array_split(np.arange(r), m)]
    last = np.cumsum(sizes) - 1
    msg = np.searchsorted(last, np.arange(r))
    return last[msg], msg


def _arrivals(s, r: int, m: int, eps: float):
    """Arrival of each of the first r results of every worker."""
    close, msg = message_layout(r, m)
    a = s[:, :, close]
    if eps:
        a = a + jnp.asarray(eps * (msg + 1), s.dtype)
    return a


def _order_stat(x, idx: int):
    """The (idx+1)-th smallest along the last axis."""
    return jnp.sort(x, axis=-1)[..., idx]


def _stats(s, schemes, n: int, k: int):
    """Per-trial completion time of every scheme from slot arrivals
    ``s`` (B, n, r_max)."""
    B = s.shape[0]
    out = []
    for sc in schemes:
        fam, r = sc["family"], int(sc["r"])
        m = sc.get("messages") or (1 if fam == "pc" else r)
        sr = _arrivals(s, r, int(m), float(sc.get("comm_eps", 0.0)))
        if fam in ("cs", "ss", "ra"):
            C = to_matrix(sc, n)
            holds = C[None, :, :] == np.arange(n)[:, None, None]  # (t, i, j)
            tau = jnp.where(holds[None], sr[:, None], jnp.inf).min(
                axis=(2, 3))                          # first copy of task t
            out.append(_order_stat(tau, k - 1))
        elif fam == "lb":
            out.append(_order_stat(sr.reshape(B, -1), k - 1))
        elif fam == "pc":
            th = 2 * math.ceil(n / r) - 1
            out.append(_order_stat(sr[:, :, 0], th - 1))
        elif fam == "pcmm":
            out.append(_order_stat(sr.reshape(B, -1), 2 * n - 2))
        else:
            raise ValueError(f"unknown family {fam!r}")
    return jnp.stack(out, axis=-1)


def _truncnorm(key, shape, mu, sigma, a, dtype):
    z = jax.random.truncated_normal(key, -a / sigma, a / sigma, shape, dtype)
    return jnp.asarray(mu, dtype) + jnp.asarray(sigma, dtype) * z


@partial(jax.jit, static_argnames=("delays", "schemes", "n", "k", "block",
                                   "dtype"))
def _block_sums(key, *, delays, schemes, n, k, block, dtype):
    d = dict(delays)
    sch = [dict(s) for s in schemes]
    r_max = max(int(s["r"]) for s in sch)
    k1, k2 = jax.random.split(key)
    T1 = _truncnorm(k1, (block, n, r_max), d["mu1"], d["sigma1"], d["a1"],
                    dtype)
    T2 = _truncnorm(k2, (block, n, r_max), d["mu2"], d["sigma2"], d["a2"],
                    dtype)
    s = jnp.cumsum(T1, axis=-1) + T2
    v = _stats(s, sch, n, k).astype(jnp.float32)
    return v.sum(axis=0), (v * v).sum(axis=0)


def _freeze(d: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items()))


def round_means(schemes, delays: dict, n: int, k: int, trials: int,
                seed: int, *, block: int = 16384, dtype=jnp.float32):
    """Mean completion time and its standard error for every scheme, over
    ``trials`` independent rounds drawn from ``seed``.  Returns
    ``{name: (mean, stderr)}``."""
    if trials % block:
        raise ValueError(f"trials={trials} must be a multiple of "
                         f"block={block}")
    frozen = tuple(_freeze(s) for s in schemes)
    fd = _freeze({key: float(delays[key])
                  for key in ("mu1", "sigma1", "a1", "mu2", "sigma2", "a2")})
    root = jax.random.PRNGKey(seed)
    s1 = np.zeros(len(schemes))
    s2 = np.zeros(len(schemes))
    for b in range(trials // block):
        p1, p2 = _block_sums(jax.random.fold_in(root, b), delays=fd,
                             schemes=frozen, n=n, k=k, block=block,
                             dtype=dtype)
        s1 += np.asarray(p1, np.float64)
        s2 += np.asarray(p2, np.float64)
    mean = s1 / trials
    var = np.maximum(s2 / trials - mean * mean, 0.0)
    se = np.sqrt(var / trials)
    return {sc["name"]: (float(mean[i]), float(se[i]))
            for i, sc in enumerate(schemes)}
