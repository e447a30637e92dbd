"""Plain Monte-Carlo of adaptive multi-round scheduling on a cluster with
persistent stragglers (arXiv:1810.09992 Secs. II-V and VI-A; Behrouzi-Far
& Soljanin, arXiv:1808.02838; Egger et al., arXiv:2304.08589).

An independent reference for the engine's rounds axis: it imports nothing
of the program and draws its own delays.

    Delays.  n workers; worker i's slot j in round t lands at
        s[i, j] = f[i] * (T1[i, 0] + ... + T1[i, j] + T2[i, j])   (eq. 1)
    with T1 ~ N(mu1, sigma1^2) truncated to mu1 +- a1 and T2 ~ N(mu2,
    sigma2^2) truncated to mu2 +- a2 (Sec. VI-C, eq. 66; mu1 and mu2 may
    be per worker), and f[i] = scale[i] * (slow if worker i is in its slow
    regime this round else 1).  ``scale`` holds fixed machine speeds
    spread geometrically over [1/sqrt(spread), sqrt(spread)] and permuted
    by ``numpy.random.default_rng(scale_seed)``.  The regime is a
    two-state chain per worker with stationary slow share p_slow and
    one-step autocorrelation ``persistence``: it starts from its
    stationary law and steps once before every round.

    Schemes, each scored on the same delays every round:
    cs, ss (static): CS (eq. 21) or SS (eq. 29) rows, worker i runs row i;
        a task arrives with its first copy and the round closes at the
        k-th distinct task;
    lb: the oracle bound (eq. 46), the k-th of all n*r slot arrivals;
    adaptive (base cs or ss): before every round the master re-assigns the base
        rows to workers from its delay estimates (greedy below); with
        ``rebalance`` it also moves whole slots between workers (below).

    Censored feedback: the master sees only the slots whose result
    arrived by that scheme's own round close.  A worker's estimate starts
    unknown (+inf); its first observation replaces it, later ones enter an
    EMA with weight beta on the past; a worker that delivered nothing keeps
    its estimate.  An observation is the mean compute delay f*T1 of the
    worker's observed slots.  Uncensored feedback (the idealized master):
    estimates start at 1, every worker is observed every round through the
    mean f*T1 of all its drawn slots, and one estimate serves every
    adaptive scheme.

    Greedy re-assignment: workers pick in order of their estimates,
    fastest first (ties: lower worker index first); each takes the untaken
    row of least discounted coverage, ties to the lowest row.  A row's
    coverage is sum_j gamma**j * cov[C[p, j]]; picking row p by a worker
    of estimate e adds gamma**j / e to the coverage of task C[p, j].

    Re-balancing: per-worker loads between 1 and the cap (the base's
    width) at a fixed total, each worker's l-th slot costing e * l: the
    total is met by the cheapest slots.  Where the observed workers cannot
    hold it, they all run at the cap and the unobserved ones keep their
    initial loads, from the highest worker index down, the rest running
    one slot.

Departures, each taken from the engine under test so that exact ties
break alike (which CS and SS produce structurally):
- a row's coverage is summed over tasks in ascending task order;
- an estimate is clamped to 1e-30 before its reciprocal is taken.
Everything is computed in ``dtype`` (float32 for the reference; the
control computes it in bfloat16); per-trial statistics are summed in
float64 on the host.
"""
from __future__ import annotations

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
    return (i + np.where(i % 2 == 0, 1, -1) * np.arange(r)[None, :]) % n


def machine_scales(n: int, spread: float, seed: int) -> np.ndarray:
    """Fixed per-worker speed factors, geometric mean 1."""
    if n == 1 or spread == 1.0:
        return np.ones(n)
    log_s = np.linspace(-0.5, 0.5, n) * np.log(spread)
    return np.exp(np.random.default_rng(seed).permutation(log_s))


def coverage_weights(C: np.ndarray, gamma: float) -> np.ndarray:
    """W[p, u] = sum_j gamma**j * [C[p, j] == u]."""
    n, r = C.shape
    W = np.zeros((n, n), np.float32)
    for p in range(n):
        for j in range(r):
            W[p, C[p, j]] += np.float32(gamma ** j)
    return W


def greedy_rows(est, W):
    """``worker_of_row`` (B, n) from estimates ``est`` (B, n)."""
    B, n = est.shape
    dt = est.dtype
    order = jnp.argsort(est, axis=-1, stable=True)
    inv = 1.0 / jnp.maximum(jnp.take_along_axis(est, order, axis=-1),
                            jnp.asarray(1e-30, dt))
    Wd = jnp.asarray(W, dt)
    rows = jnp.arange(n)[None, :]

    def pick(carry, t):
        cov, taken, w_of_row = carry
        score = cov[:, 0:1] * Wd[:, 0][None, :]
        for u in range(1, n):
            score = score + cov[:, u:u + 1] * Wd[:, u][None, :]
        score = jnp.where(taken, jnp.inf, score)
        p = jnp.argmin(score, axis=-1)
        hit = rows == p[:, None]
        w_of_row = jnp.where(hit, order[:, t][:, None], w_of_row)
        cov = cov + Wd[p] * inv[:, t][:, None]
        return (cov, taken | hit, w_of_row), None

    init = (jnp.zeros((B, n), dt), jnp.zeros((B, n), bool),
            jnp.zeros((B, n), jnp.int32))
    (_, _, w_of_row), _ = jax.lax.scan(pick, init, jnp.arange(n))
    return w_of_row


def rebalanced_loads(est, l0: np.ndarray, cap: int):
    """Per-worker loads (B, n) in [1, cap] summing to ``sum(l0)``."""
    B, n = est.shape
    extra = int(l0.sum()) - n
    j = jnp.arange(2, cap + 1, dtype=est.dtype)
    cost = (est[:, :, None] * j).reshape(B, -1)
    fin = jnp.isfinite(cost)
    thr = jnp.sort(cost, axis=-1)[:, extra - 1:extra] if extra else None
    if thr is None:
        return jnp.ones((B, n), jnp.int32)
    take = ((cost <= thr) & fin).reshape(B, n, cap - 1).sum(-1)
    # the total beyond what the observed workers can run (thr = +inf)
    short = extra - fin.sum(-1)
    unseen = ~jnp.isfinite(est)
    room = jnp.where(unseen, jnp.asarray(l0 - 1, jnp.int32), 0)
    above = jnp.cumsum(room[:, ::-1], axis=-1)[:, ::-1] - room
    keep = jnp.clip(short[:, None] - above, 0, room)
    return 1 + jnp.where(unseen, keep, take).astype(jnp.int32)


def _kth(x, k: int):
    return jnp.sort(x, axis=-1)[..., k - 1]


def _first_copies(arr, tasks, n: int):
    """Arrival of each task at its first copy: ``arr`` and ``tasks`` (B,
    n, r), masked slots carry +inf."""
    hit = tasks[:, None] == jnp.arange(n)[None, :, None, None]
    return jnp.where(hit, arr[:, None], jnp.inf).min(axis=(2, 3))


def _setup(schemes, n: int, gamma: float):
    out = []
    for sc in schemes:
        r = int(sc["r"])
        d = dict(name=sc["name"], family=sc["family"], r=r,
                 adaptive=bool(sc.get("adaptive")),
                 rebalance=bool(sc.get("rebalance")))
        if sc["family"] in ("cs", "ss"):
            d["C"] = (cyclic if sc["family"] == "cs" else staircase)(n, r)
            d["W"] = coverage_weights(d["C"], gamma)
        elif sc["family"] != "lb":
            raise ValueError(f"unknown family {sc['family']!r}")
        if d["rebalance"]:
            d["l0"] = np.broadcast_to(np.asarray(sc["loads"], np.int64), (n,))
        out.append(d)
    return out


def _round(schemes, ests, T1, T2, t, *, n, k, beta, censored):
    """Score every scheme on one round's delays (B, n, r_max); returns
    (times {name: (B,)}, rows {name: (B, n)}, new estimates)."""
    dt = T1.dtype
    s = jnp.cumsum(T1, axis=-1) + T2
    times, rows, new = {}, {}, dict(ests)
    for sc in schemes:
        nm, r = sc["name"], sc["r"]
        arr = s[..., :r]
        if sc["family"] == "lb":
            times[nm] = _kth(arr.reshape(arr.shape[0], -1), k)
            continue
        C = jnp.asarray(sc["C"])
        if not sc["adaptive"]:
            tasks = jnp.broadcast_to(C, arr.shape)
            times[nm] = _kth(_first_copies(arr, tasks, n), k)
            continue
        est = ests[nm if censored else "shared"]
        w_of_row = greedy_rows(est, sc["W"])
        rows[nm] = w_of_row
        row_of_worker = jnp.argsort(w_of_row, axis=-1)
        tasks = C[row_of_worker]                            # (B, n, r)
        if sc["rebalance"]:
            load = rebalanced_loads(est, sc["l0"], r)
            arr = jnp.where(jnp.arange(r) < load[..., None], arr, jnp.inf)
        done = _kth(_first_copies(arr, tasks, n), k)
        times[nm] = done
        if censored:
            seen = (arr <= done[:, None, None]) & jnp.isfinite(arr)
            cnt = seen.sum(-1)
            obs = jnp.where(cnt > 0, jnp.where(seen, T1[..., :r], 0.0)
                            .sum(-1) / jnp.maximum(cnt, 1), 0.0).astype(dt)
            upd = jnp.where(jnp.isfinite(est),
                            beta * est + (1.0 - beta) * obs, obs)
            new[nm] = jnp.where(cnt > 0, upd, est)
    if not censored and "shared" in ests:
        est, obs = ests["shared"], T1.mean(axis=-1)
        upd = jnp.where(t == 0, obs, beta * est + (1.0 - beta) * obs)
        new["shared"] = jnp.where(jnp.isfinite(obs), upd, est)
    return times, rows, new


def _init_estimates(schemes, B: int, n: int, censored: bool, dt):
    ad = [sc["name"] for sc in schemes if sc["adaptive"]]
    if not ad:
        return {}
    if censored:
        return {nm: jnp.full((B, n), jnp.inf, dt) for nm in ad}
    return {"shared": jnp.ones((B, n), dt)}


def _truncnorm(key, shape, mu, sigma, a, dtype):
    z = jax.random.truncated_normal(key, -a / sigma, a / sigma, shape, dtype)
    mu = jnp.asarray(np.broadcast_to(np.asarray(mu, np.float64),
                                     (shape[1],)), dtype)
    return mu[None, :, None] + jnp.asarray(sigma, dtype) * z


@partial(jax.jit, static_argnames=("schemes", "delays", "process", "n", "k",
                                   "rounds", "block", "beta", "gamma",
                                   "censored", "dtype"))
def _block_sums(key, *, schemes, delays, process, n, k, rounds, block, beta,
                gamma, censored, dtype):
    sch = _setup([dict(s) for s in schemes], n, gamma)
    d, p = dict(delays), dict(process)
    r_max = max(sc["r"] for sc in sch)
    scale = jnp.asarray(machine_scales(n, p["spread"], p["scale_seed"]),
                        dtype)
    p_fs = (1.0 - p["persistence"]) * p["p_slow"]
    p_sf = (1.0 - p["persistence"]) * (1.0 - p["p_slow"])
    k0, kr = jax.random.split(key)
    slow0 = jax.random.bernoulli(k0, p["p_slow"], (block, n))

    def body(carry, xs):
        slow, ests = carry
        key_t, t = xs
        ku, k1, k2 = jax.random.split(key_t, 3)
        u = jax.random.uniform(ku, (block, n))
        slow = jnp.where(slow, u >= p_sf, u < p_fs)
        f = (jnp.where(slow, jnp.asarray(p["slow"], dtype),
                       jnp.asarray(1.0, dtype)) * scale)[..., None]
        shape = (block, n, r_max)
        T1 = f * _truncnorm(k1, shape, d["mu1"], d["sigma1"], d["a1"], dtype)
        T2 = f * _truncnorm(k2, shape, d["mu2"], d["sigma2"], d["a2"], dtype)
        times, _, ests = _round(sch, ests, T1, T2, t, n=n, k=k, beta=beta,
                                censored=censored)
        v = jnp.stack([times[sc["name"]] for sc in sch]).astype(jnp.float32)
        return (slow, ests), (v.sum(-1), (v * v).sum(-1))

    init = (slow0, _init_estimates(sch, block, n, censored, dtype))
    _, (s1, s2) = jax.lax.scan(body, init, (jax.random.split(kr, rounds),
                                            jnp.arange(rounds)))
    return s1, s2                              # (rounds, schemes) each


def _freeze(d: dict) -> tuple:
    return tuple(sorted((key, tuple(v) if isinstance(v, list) else v)
                        for key, v in d.items()))


def round_means(schemes, delays: dict, process: dict, n: int, k: int,
                rounds: int, trials: int, seed: int, *, beta: float = 0.7,
                gamma: float = 0.5, censored: bool = True,
                block: int = 16384, dtype=jnp.float32):
    """Mean completion time of every round and its standard error for
    every scheme, over ``trials`` independent runs of ``rounds`` rounds
    drawn from ``seed``.  Returns ``{name: (means (rounds,), stderrs
    (rounds,))}``."""
    if trials % block:
        raise ValueError(f"trials={trials} must be a multiple of "
                         f"block={block}")
    frozen = tuple(_freeze(s) for s in schemes)
    fd = _freeze({key: delays[key] for key in ("mu1", "sigma1", "a1", "mu2",
                                               "sigma2", "a2")})
    fp = _freeze({key: process[key] for key in ("spread", "scale_seed",
                                                "p_slow", "persistence",
                                                "slow")})
    root = jax.random.PRNGKey(seed)
    s1 = np.zeros((rounds, len(schemes)))
    s2 = np.zeros((rounds, len(schemes)))
    with jax.default_matmul_precision("highest"):
        for b in range(trials // block):
            p1, p2 = _block_sums(
                jax.random.fold_in(root, b), schemes=frozen, delays=fd,
                process=fp, n=n, k=k, rounds=rounds, block=block,
                beta=float(beta), gamma=float(gamma),
                censored=bool(censored), dtype=dtype)
            s1 += np.asarray(p1, np.float64)
            s2 += np.asarray(p2, np.float64)
    mean = s1 / trials
    se = np.sqrt(np.maximum(s2 / trials - mean * mean, 0.0) / trials)
    return {sc["name"]: (mean[:, i], se[:, i])
            for i, sc in enumerate(schemes)}


def replay(schemes, T1, T2, n: int, k: int, *, beta: float = 0.7,
           gamma: float = 0.5, censored: bool = True) -> dict:
    """The rounds of recorded delays: ``T1``, ``T2`` (rounds, trials, n,
    r_max), the regime and machine factors already applied.  Returns
    ``{name: {"times": (trials, rounds)}}``, with ``"rows"`` (trials,
    rounds, n), the worker of each row, and ``"est"`` (trials, rounds, n),
    the estimates the round was assigned from, for adaptive schemes."""
    sch = _setup(schemes, n, gamma)
    T1 = jnp.asarray(T1, jnp.float32)
    T2 = jnp.asarray(T2, jnp.float32)

    def body(ests, xs):
        t1, t2, t = xs
        times, rows, new = _round(sch, ests, t1, t2, t, n=n, k=k, beta=beta,
                                  censored=censored)
        seen = {sc["name"]: ests[sc["name"] if censored else "shared"]
                for sc in sch if sc["adaptive"]}
        return new, (times, rows, seen)

    init = _init_estimates(sch, T1.shape[1], n, censored, jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, (times, rows, ests) = jax.jit(
            lambda a, b: jax.lax.scan(body, init,
                                      (a, b, jnp.arange(a.shape[0]))))(T1, T2)
    out = {}
    for sc in sch:
        nm = sc["name"]
        out[nm] = {"times": np.asarray(times[nm]).T}
        if sc["adaptive"]:
            out[nm]["rows"] = np.asarray(rows[nm]).transpose(1, 0, 2)
            out[nm]["est"] = np.asarray(ests[nm]).transpose(1, 0, 2)
    return out
