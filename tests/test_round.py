"""The one round close (``repro.core.round``) against the scatter-min
reference of ``completion.py`` and, for static schedules, against the
engine's rounds scan replaying the same tables: winner weights, effective
completion, per-task arrivals, the censored-feedback arrivals, and the
deadline accounting the trainer and the aggregator used to compute on
their own."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DelayTrace, RoundConfig, TraceProcess,
                        first_k_distinct_mask, loads_of_matrix,
                        make_round_close, make_scheduler, mask_matrix_loads,
                        message_arrival_times, sweep_rounds,
                        task_arrival_times)

N, K, ROUNDS = 6, 4, 6

CONFIGS = {
    "dense-cs": dict(kind="cs", r=2),
    "ss-m2": dict(kind="ss", r=3, messages=2),
    "ragged": dict(kind="cs", r=3, loads=(1, 2, 3, 2, 1, 3)),
    "comm-eps": dict(kind="cs", r=2, comm_eps=0.25),
    "close-partial": dict(kind="cs", r=2, deadline=1.0,
                          deadline_policy="close_partial"),
    "reissue": dict(kind="cs", r=2, adaptive=True, deadline=1.0,
                    deadline_policy="reissue"),
    "rebalance": dict(kind="cs", r=3, adaptive=True, rebalance=True,
                      loads=(2,) * N),
    "rebalance-m2": dict(kind="cs", r=3, adaptive=True, rebalance=True,
                         loads=(2,) * N, messages=2),
}


def _tables(r):
    """(ROUNDS, 1, N, r) delay tables with a dead worker (round 2) and a
    worker stuck after its first slot (round 3)."""
    rng = np.random.default_rng(0)
    T1 = (rng.exponential(0.5, (ROUNDS, 1, N, r)) + 0.1).astype(np.float32)
    T2 = rng.exponential(0.3, (ROUNDS, 1, N, r)).astype(np.float32)
    T1[2, 0, 1] = np.inf
    T1[3, 0, 0, 1:] = np.inf
    return T1, T2


def _plan(cfg, rng):
    """A row permutation and, under rebalance, a load vector that keeps
    the budget ``sum(loads)`` within [1, r] per worker."""
    row = rng.permutation(N) if cfg.adaptive else np.arange(N)
    if not cfg.rebalance:
        return row, cfg.load_vector
    lw = np.ones(N, np.int64)
    extra = sum(cfg.loads) - N
    while extra:
        w = rng.integers(N)
        if lw[w] < cfg.width:
            lw[w] += 1
            extra -= 1
    return row, lw


@pytest.mark.parametrize("name", list(CONFIGS))
def test_round_close_matches_reference(name):
    cfg = RoundConfig(n=N, k=K, **CONFIGS[name])
    T1a, T2a = _tables(cfg.width)
    close = jax.jit(make_round_close(cfg))
    closing = cfg.deadline is not None and cfg.deadline_policy != "wait"
    rng = np.random.default_rng(1)
    t_effs, realized, missed = [], [], []
    for t in range(ROUNDS):
        row, lw = _plan(cfg, rng)
        rc = close(T1a[t], T2a[t], jnp.asarray(row), jnp.asarray(lw))
        # reference: the worker-major effective matrix and its masked
        # per-message arrivals through the scatter-min
        base = cfg.base_matrix() if cfg.rebalance else cfg.to_matrix()
        C_eff = base[row]
        if cfg.rebalance:
            C_eff = mask_matrix_loads(C_eff, lw)
        s = message_arrival_times(jnp.asarray(T1a[t]), jnp.asarray(T2a[t]),
                                  cfg.n_messages,
                                  loads=loads_of_matrix(C_eff),
                                  comm_eps=cfg.comm_eps)
        w_ref, t_ref = first_k_distinct_mask(
            C_eff, s, N, K, deadline=cfg.deadline if closing else None)
        w_ref, t_ref = np.asarray(w_ref[0]), np.asarray(t_ref[0])
        tau_ref = np.asarray(task_arrival_times(C_eff, s, N)[0])
        np.testing.assert_array_equal(np.asarray(rc.weights), w_ref)
        np.testing.assert_array_equal(np.asarray(rc.t_eff), t_ref)
        np.testing.assert_array_equal(np.asarray(rc.tau), tau_ref)
        np.testing.assert_array_equal(np.asarray(rc.arr_w),
                                      np.asarray(s[0]))
        np.testing.assert_array_equal(
            np.asarray(rc.delivered),
            (tau_ref <= t_ref) & np.isfinite(tau_ref))
        # the two deadline-miss forms the round close replaces
        wsum = float(w_ref.sum())
        if cfg.deadline is None:
            old_trainer = old_aggregator = False
        elif closing:
            old_trainer = old_aggregator = wsum < K
        else:
            old_trainer = bool(t_ref > cfg.deadline)
            old_aggregator = float(t_ref) > cfg.deadline
        assert bool(rc.missed) == old_trainer == old_aggregator
        by = (tau_ref <= cfg.deadline).sum() if closing else \
            np.isfinite(tau_ref).sum()
        assert float(rc.realized) == min(by, K)
        t_effs.append(np.asarray(rc.t_eff))
        realized.append(float(rc.realized))
        missed.append(float(rc.missed))
    if closing:
        assert 0 < sum(missed) < ROUNDS          # the deadline bites
    if cfg.adaptive:
        assert make_scheduler(cfg) is not None
        return
    # static schedules: the engine replays the same tables bit for bit
    res = sweep_rounds([cfg.to_scheme_spec("s")],
                       TraceProcess(DelayTrace(T1a, T2a)), N, rounds=ROUNDS,
                       trials=1, **cfg.sweep_rounds_kwargs())
    np.testing.assert_array_equal(res.per_round["s"].astype(np.float32),
                                  np.stack(t_effs))
    if cfg.deadline is not None:
        deg = res.degradation["s"]
        np.testing.assert_array_equal(np.asarray(deg["realized_k"]),
                                      realized)
        np.testing.assert_array_equal(np.asarray(deg["missed"]), missed)
