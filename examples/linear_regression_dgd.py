"""End-to-end reproduction of the paper's Section VI scenario: distributed
linear regression with DGD under straggler scheduling.

Runs the full loop for CS / SS / RA / adaptive / PC / PCMM on a round-aware
virtual cluster: every scheme really computes h(X_i) = X_i X_i^T theta (the
Pallas gram_matvec kernel for the uncoded schemes), the coded schemes
really encode/decode, the master applies eq. (61)/(49), and the virtual
clock advances by each round's completion time.  The uncoded schemes run
through ``StragglerAggregator``'s round API, so with ``--cluster markov``
the same loop exercises heterogeneous, persistent stragglers and the
feedback-driven adaptive schedule.  Emits per-scheme loss-vs-wall-clock
curve rows (``curve,<scheme>,<iter>,<wallclock_ms>,<loss>``) plus the
final table.

Run:  PYTHONPATH=src python examples/linear_regression_dgd.py
          [--iters 100] [--cluster markov --persistence 0.95 --spread 3]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import regression_config
from repro.core import (RoundConfig, StragglerAggregator, as_process,
                        ec2_cluster, ec2_like, slot_arrival_times,
                        pc_encode, pc_worker_compute, pc_decode,
                        pc_threshold, pcmm_encode, pcmm_worker_compute,
                        pcmm_decode, pcmm_threshold)
from repro.data import regression_dataset, regression_tasks
from repro.kernels.ops import batched_gram_matvec


def loss_of(theta, X, y):
    res = X @ theta - y
    return float(res @ res) / X.shape[0]


def run_uncoded(spec, process, Xs_cols, Xty_parts, N, X, y, iters, lr, *,
                curve_every=10, label="?", seed=0):
    """The paper's uncoded DGD loop (Table I rows) through the round API:
    the aggregator holds the cluster's straggler state across iterations
    and (optionally) re-permutes the schedule rows from delay feedback."""
    n, r = spec.n, spec.width
    d = Xs_cols.shape[1]
    theta = np.zeros(d, np.float32)
    agg = StragglerAggregator(spec, process)
    key = jax.random.PRNGKey(seed)
    clock, curve = 0.0, []
    for it in range(iters):
        key, kd = jax.random.split(key)
        C = agg.current_matrix()
        w, t_done = agg.round_mask(kd)
        clock += float(t_done)
        # workers: sequential h(X_i) evaluations (Pallas kernel)
        hs = np.asarray(batched_gram_matvec(Xs_cols, jnp.asarray(theta)))
        # master: eq. (61) over the k winning distinct tasks
        wmask = np.asarray(w) > 0
        sel = sorted({int(C[i, j]) for i in range(n) for j in range(r)
                      if wmask[i, j]})
        assert len(sel) == spec.k
        grad = 2 * n / (spec.k * N) * sum(hs[p] - Xty_parts[p] for p in sel)
        theta = theta - lr * grad
        if it % curve_every == 0 or it == iters - 1:
            curve.append((it, clock, loss_of(theta, X, y)))
    for it, c, l in curve:
        print(f"curve,{label},{it},{c * 1e3:.4f},{l:.5f}")
    return theta, clock


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--cluster", default="iid", choices=("iid", "markov"))
    ap.add_argument("--persistence", type=float, default=0.95)
    ap.add_argument("--spread", type=float, default=3.0)
    args = ap.parse_args()
    rc = regression_config()
    n, r, k, lr = rc.n, rc.r, rc.k, rc.lr
    key = jax.random.PRNGKey(0)
    X, y, _ = regression_dataset(key, rc.N, rc.d)
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    Xs, ys = regression_tasks(jnp.asarray(X), jnp.asarray(y), n)
    Xs_cols = jnp.asarray(np.asarray(Xs).transpose(0, 2, 1))  # (n, d, b)
    Xty_parts = np.stack([np.asarray(Xs[i]).T @ np.asarray(ys[i])
                          for i in range(n)])
    Xty = Xty_parts.sum(0)
    N = n * Xs.shape[1]
    if args.cluster == "markov":
        process = ec2_cluster(n, spread=args.spread, p_slow=0.25,
                              persistence=args.persistence, slow=8.0,
                              base=ec2_like(n, seed=1), seed=1)
    else:
        process = as_process(ec2_like(n, seed=1))
    print(f"paper scenario: N={rc.N} d={rc.d} n={n} r={r} k={k} "
          f"iters={args.iters} cluster={args.cluster}")
    print(f"{'scheme':8s} {'final loss':>12s} {'virtual time':>14s}")

    rows = []
    for name, sched, adaptive in (("CS", "cs", False), ("SS", "ss", False),
                                  ("RA", "ra", False),
                                  ("ADAPT", "cs", True)):
        spec = RoundConfig(n=n, k=k, kind=sched,
                           r=n if sched == "ra" else r, adaptive=adaptive)
        theta, clock = run_uncoded(spec, process, Xs_cols, Xty_parts, N,
                                   X, y, args.iters, lr, label=name)
        rows.append((name, loss_of(theta, X, y), clock))

    # --- PC: one coded message per worker, threshold 2*ceil(n/r)-1 --------
    # Coded baselines advance the SAME kind of round-aware process (fresh
    # state, own key stream): T1/T2 realizations persist across rounds.
    theta = np.zeros(rc.d, np.float32)
    Xt, alphas, _ = pc_encode(np.asarray(Xs_cols, np.float64), r)
    clock = 0.0
    keyp = jax.random.PRNGKey(7)
    pstate = process.init(jax.random.PRNGKey(70)[None], n)
    for _ in range(args.iters):
        keyp, kd = jax.random.split(keyp)
        pstate, T1, T2 = process.step(pstate, kd[None], n, r)
        t_w = np.asarray(T1.sum(-1) + T2[..., -1])[0]
        kth = pc_threshold(n, r)
        order = np.argsort(t_w)[:kth]
        clock += float(np.sort(t_w)[kth - 1])
        res = np.stack([pc_worker_compute(Xt[i], theta) for i in order])
        xxt = pc_decode(res, alphas[order], n, r)
        theta = theta - lr * 2 / N * (xxt - Xty)
    rows.append(("PC", loss_of(theta, X, y), clock))

    # --- PCMM: sequential coded messages, threshold 2n-1 ------------------
    theta = np.zeros(rc.d, np.float32)
    Xh, betas = pcmm_encode(np.asarray(Xs_cols, np.float64), r)
    clock = 0.0
    keyp = jax.random.PRNGKey(9)
    pstate = process.init(jax.random.PRNGKey(90)[None], n)
    for _ in range(args.iters):
        keyp, kd = jax.random.split(keyp)
        pstate, T1, T2 = process.step(pstate, kd[None], n, r)
        s = np.asarray(slot_arrival_times(T1, T2))[0].reshape(-1)
        need = pcmm_threshold(n)
        order = np.argsort(s)[:need]
        clock += float(np.sort(s)[need - 1])
        res = np.stack([pcmm_worker_compute(
            Xh[o // r, o % r], theta) for o in order])
        pts = np.array([betas[o // r, o % r] for o in order])
        xxt = pcmm_decode(res, pts, n)
        theta = theta - lr * 2 / N * (xxt - Xty)
    rows.append(("PCMM", loss_of(theta, X, y), clock))

    for name, loss, clock in rows:
        print(f"{name:8s} {loss:12.5f} {clock * 1e3:11.3f} ms")


if __name__ == "__main__":
    main()
