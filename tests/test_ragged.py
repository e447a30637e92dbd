"""Tests for ragged per-worker loads end-to-end (ISSUE-4).

Covers the acceptance points:
  (a) ragged constructions (CS/SS/RA + validation + load inference);
  (b) uniform-``loads`` specs reproduce the dense path BIT-EXACTLY under
      common random numbers for every scheme kind (to/tau/adaptive/lb),
      with and without a message budget;
  (c) ragged engine paths match independent numpy oracles (task arrivals,
      order statistics, per-worker message grouping, ragged lower bound);
  (d) ``greedy_load_rebalance``: budget conservation, bounds, slow workers
      shed slots, no-feedback fixed point, numpy/JAX batch agreement;
  (e) chunk invariance of ``sweep_rounds`` with re-balanced loads, and the
      rebalance scheme beating permutation-only adaptation on a
      heterogeneous persistent cluster;
  (f) ragged rounds through the aggregator/train API (masked slots carry
      zero winner weight; eq.-(61) weighting stays unbiased);
  (g) the per-message overhead ``comm_eps`` (Ozfatura trade-off) against a
      numpy oracle and its effect on the optimal budget.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MASKED, AdaptiveScheduler, MarkovRegimeProcess,
                        RoundSpec, ShiftedExponentialDelays,
                        StragglerAggregator, adaptive_spec, clear_cache,
                        completion_samples, cyclic_to_matrix, ec2_cluster,
                        greedy_load_rebalance, greedy_load_rebalance_batch,
                        heterogeneous_scales, lb_spec, loads_of_matrix,
                        mask_matrix_loads, message_arrival_times,
                        message_comm_delays, message_boundaries,
                        message_group_sizes, random_assignment_to_matrix,
                        scenario1, staircase_to_matrix, sweep, sweep_rounds,
                        task_arrival_samples, tau_spec, theorem1_mean_mc,
                        lower_bound_mean_mc, to_matrix, to_spec,
                        trajectory_samples, validate_to_matrix)


LOADS = (3, 1, 2, 3, 1, 2)
N6 = 6


def _oracle_draws(model, n, r, trials, seed):
    # the engine's per-trial key convention: fold_in(base, trial id)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(seed), jnp.arange(trials, dtype=jnp.int32))
    T1s, T2s = [], []
    for i in range(trials):
        T1, T2 = model.sample(keys[i], 1, n, r)
        T1s.append(np.asarray(T1)[0])
        T2s.append(np.asarray(T2)[0])
    return np.stack(T1s), np.stack(T2s)


# ------------------------- (a) ragged constructions --------------------------

class TestRaggedConstructions:
    def test_cs_ss_ragged_shapes_and_masks(self):
        for build in (cyclic_to_matrix, staircase_to_matrix):
            C = build(N6, loads=LOADS)
            assert C.shape == (N6, max(LOADS))
            assert (loads_of_matrix(C) == np.asarray(LOADS)).all()
            validate_to_matrix(C, N6, loads=LOADS)
            # active prefix rows match the dense construction
            D = build(N6, max(LOADS))
            for i, l in enumerate(LOADS):
                assert (C[i, :l] == D[i, :l]).all()
                assert (C[i, l:] == MASKED).all()

    def test_slot0_diagonal_keeps_coverage(self):
        for build in (cyclic_to_matrix, staircase_to_matrix):
            C = build(N6, loads=LOADS)
            assert sorted(C[:, 0].tolist()) == list(range(N6))

    def test_ragged_ra_coverage_and_distinctness(self):
        C = random_assignment_to_matrix(8, loads=(2, 3, 1, 8, 4, 1, 2, 5),
                                        seed=3)
        validate_to_matrix(C, 8)
        assert sorted(C[:, 0].tolist()) == list(range(8))   # diagonal start

    def test_to_matrix_passes_loads(self):
        C = to_matrix("cs", N6, loads=LOADS)
        assert (loads_of_matrix(C) == np.asarray(LOADS)).all()

    def test_wider_grid_than_max_load(self):
        C = cyclic_to_matrix(N6, 5, loads=LOADS)
        assert C.shape == (N6, 5)
        assert (loads_of_matrix(C) == np.asarray(LOADS)).all()

    def test_mask_matrix_loads_and_inference_errors(self):
        C = cyclic_to_matrix(4, 3)
        M = mask_matrix_loads(C, [2, 1, 3, 1])
        assert (loads_of_matrix(M) == [2, 1, 3, 1]).all()
        bad = C.copy()
        bad[0, 0] = MASKED                       # interior mask
        with pytest.raises(ValueError, match="trailing"):
            loads_of_matrix(bad)
        with pytest.raises(ValueError, match="active"):
            loads_of_matrix(np.full((2, 2), MASKED))
        with pytest.raises(ValueError):
            cyclic_to_matrix(4, loads=[0, 1, 1, 1])     # load 0
        with pytest.raises(ValueError):
            cyclic_to_matrix(4, loads=[1, 1, 1])        # wrong length
        with pytest.raises(ValueError):
            cyclic_to_matrix(4, 2, loads=[3, 1, 1, 1])  # load > width
        with pytest.raises(ValueError, match="match"):
            validate_to_matrix(mask_matrix_loads(C, [2, 1, 3, 1]), 4,
                               loads=[1, 1, 3, 1])


# ------------------ (b) uniform loads == dense, bit-exact --------------------

class TestUniformLoadsParity:
    @pytest.mark.parametrize("messages", [None, 1, 2])
    def test_to_and_lb_bitexact(self, messages):
        n, r, k, trials = 8, 4, 6, 1200
        m = scenario1()
        C = staircase_to_matrix(n, r)
        dense = completion_samples(to_spec("x", C, messages=messages), m, n,
                                   trials=trials, seed=3, k=k)
        ragged = completion_samples(
            to_spec("x", C, messages=messages, loads=[r] * n), m, n,
            trials=trials, seed=3, k=k)
        assert (np.asarray(dense) == np.asarray(ragged)).all()
        dlb = completion_samples(lb_spec(r, messages=messages), m, n,
                                 trials=trials, seed=3, k=k)
        rlb = completion_samples(lb_spec(messages=messages, loads=[r] * n),
                                 m, n, trials=trials, seed=3, k=k)
        assert (np.asarray(dlb) == np.asarray(rlb)).all()

    def test_tau_bitexact(self):
        n, r, trials = 8, 4, 800
        m = scenario1()
        C = cyclic_to_matrix(n, r)
        a = task_arrival_samples(C, m, trials=trials, seed=1)
        b = task_arrival_samples(C, m, trials=trials, seed=1, loads=[r] * n)
        assert (np.asarray(a) == np.asarray(b)).all()

    def test_adaptive_bitexact_in_rounds(self):
        n, r, k = 6, 3, 5
        proc = MarkovRegimeProcess(base=scenario1(),
                                   worker_scale=heterogeneous_scales(n, 2.0),
                                   persistence=0.9)
        C = cyclic_to_matrix(n, r)
        a = trajectory_samples(adaptive_spec("a", C), proc, n, rounds=4,
                               k=k, trials=200, seed=0,
                               censored_feedback=True)
        b = trajectory_samples(adaptive_spec("a", C, loads=[r] * n), proc,
                               n, rounds=4, k=k, trials=200, seed=0,
                               censored_feedback=True)
        assert (np.asarray(a) == np.asarray(b)).all()

    def test_uniform_spec_is_canonical_dense(self):
        C = cyclic_to_matrix(6, 3)
        assert to_spec("x", C, loads=[3] * 6) == to_spec("x", C)
        assert lb_spec(3, loads=[3] * 6) == lb_spec(3)


# ---------------------- (c) ragged engine vs numpy oracle --------------------

class TestRaggedOracles:
    def _setup(self, trials=250, seed=11):
        model = ShiftedExponentialDelays()
        Cr = cyclic_to_matrix(N6, loads=LOADS)
        T1, T2 = _oracle_draws(model, N6, max(LOADS), trials, seed)
        s = np.cumsum(T1, -1) + T2
        return model, Cr, s

    def test_ragged_task_arrivals_and_completion(self):
        model, Cr, s = self._setup()
        trials = s.shape[0]
        tau = np.full((trials, N6), np.inf)
        for w in range(N6):
            for j in range(LOADS[w]):
                tau[:, Cr[w, j]] = np.minimum(tau[:, Cr[w, j]], s[:, w, j])
        got_tau = np.asarray(task_arrival_samples(Cr, model, trials=trials,
                                                  seed=11))
        np.testing.assert_allclose(got_tau, tau, rtol=1e-6)
        for k in (1, 4, 6):
            got = np.asarray(completion_samples(to_spec("x", Cr), model, N6,
                                                trials=trials, seed=11, k=k))
            np.testing.assert_allclose(got, np.sort(tau, -1)[:, k - 1],
                                       rtol=1e-6)

    def test_ragged_lower_bound(self):
        model, Cr, s = self._setup()
        trials = s.shape[0]
        act = np.concatenate([s[:, w, :LOADS[w]] for w in range(N6)], axis=1)
        assert act.shape[1] == sum(LOADS)
        for k in (2, 5):
            got = np.asarray(completion_samples(lb_spec(loads=LOADS), model,
                                                N6, trials=trials, seed=11,
                                                k=k))
            np.testing.assert_allclose(got, np.sort(act, -1)[:, k - 1],
                                       rtol=1e-6)

    @pytest.mark.parametrize("messages", [1, 2])
    def test_ragged_message_grouping(self, messages):
        """Worker w groups its loads[w] active slots into
        min(messages, loads[w]) messages — per-worker closing slots."""
        model, Cr, s = self._setup()
        trials = s.shape[0]
        s_msg = np.full_like(s, np.inf)
        for w in range(N6):
            l = LOADS[w]
            mi = min(messages, l)
            bounds = message_boundaries(l, mi)
            smap = bounds[np.searchsorted(bounds, np.arange(l))]
            s_msg[:, w, :l] = s[:, w, smap]
        tau = np.full((trials, N6), np.inf)
        for w in range(N6):
            for j in range(LOADS[w]):
                tau[:, Cr[w, j]] = np.minimum(tau[:, Cr[w, j]],
                                              s_msg[:, w, j])
        got = np.asarray(completion_samples(
            to_spec("x", Cr, messages=messages), model, N6, trials=trials,
            seed=11, k=4))
        np.testing.assert_allclose(got, np.sort(tau, -1)[:, 3], rtol=1e-6)
        # engine message_arrival_times agrees with the same oracle
        T1, T2 = _oracle_draws(model, N6, max(LOADS), 16, seed=11)
        arr = np.asarray(message_arrival_times(jnp.asarray(T1),
                                               jnp.asarray(T2), messages,
                                               loads=LOADS))
        s16 = np.cumsum(T1, -1) + T2
        for w in range(N6):
            l = LOADS[w]
            mi = min(messages, l)
            bounds = message_boundaries(l, mi)
            smap = bounds[np.searchsorted(bounds, np.arange(l))]
            np.testing.assert_allclose(arr[:, w, :l], s16[:, w, smap],
                                       rtol=1e-6)
            assert np.isinf(arr[:, w, l:]).all()

    def test_ragged_theorem1_and_lb_mean(self):
        n = 5
        loads = (2, 1, 3, 1, 2)
        model = ShiftedExponentialDelays()
        Cr = cyclic_to_matrix(n, loads=loads)
        k = 4
        direct = np.asarray(completion_samples(to_spec("x", Cr), model, n,
                                               trials=20000, seed=0,
                                               k=k)).mean()
        thm = theorem1_mean_mc(Cr, model, k, tmax=4e-3, trials=20000, seed=0)
        assert np.isclose(thm, direct, rtol=0.02)
        lbm = lower_bound_mean_mc(model, n, k, loads=loads, trials=20000,
                                  seed=0)
        assert 0 < lbm <= direct + 1e-9

    def test_coverage_validation(self):
        """A ragged schedule that cannot deliver k distinct tasks is
        rejected up front instead of returning +inf means."""
        C = np.array([[0, 1], [0, MASKED], [1, MASKED]])   # covers 2 tasks
        m = scenario1()
        with pytest.raises(ValueError, match="covers only"):
            sweep([to_spec("x", C)], m, 3, trials=8, ks=3)
        with pytest.raises(ValueError, match="covers only"):
            sweep_rounds([to_spec("x", C)], m, 3, rounds=2, k=3, trials=8)


# ----------------------- (d) greedy load re-balancing ------------------------

class TestGreedyLoadRebalance:
    def test_conserves_budget_and_bounds(self):
        rng = np.random.default_rng(0)
        for _ in range(10):
            n = int(rng.integers(3, 12))
            r_max = int(rng.integers(2, 8))
            loads = rng.integers(1, r_max + 1, n)
            est = rng.random(n) + 0.01
            out = greedy_load_rebalance(est, loads, r_max=r_max)
            assert out.sum() == loads.sum()
            assert out.min() >= 1 and out.max() <= r_max

    def test_slow_workers_shed_slots(self):
        est = np.array([1.0, 1.0, 9.0, 1.0, 9.0, 1.0])
        out = greedy_load_rebalance(est, [3] * 6, r_max=6)
        assert out[2] < 3 and out[4] < 3          # slow shed
        assert out[[0, 1, 3, 5]].max() > 3        # fast gained
        assert out.sum() == 18

    def test_no_feedback_is_fixed_point(self):
        for est in (None, np.ones(6), np.full(6, np.inf)):
            out = greedy_load_rebalance(est, [3] * 6, r_max=6)
            assert (out == 3).all()

    def test_censored_inf_estimates_shed_to_min(self):
        est = np.array([1.0, np.inf, 1.0, np.inf])
        out = greedy_load_rebalance(est, [2] * 4, r_max=4)
        assert (out[[1, 3]] == 1).all()           # never-seen -> min load
        assert out.sum() == 8

    def test_numpy_and_batch_agree(self):
        rng = np.random.default_rng(1)
        loads = np.array([2, 3, 1, 2, 4, 2])
        est = rng.random((5, 6)) + 0.05
        got = np.asarray(greedy_load_rebalance_batch(jnp.asarray(est, jnp.float32),
                                                     loads, r_max=5))
        for b in range(5):
            ref = greedy_load_rebalance(est[b], loads, r_max=5)
            assert (got[b] == ref).all(), b

    def test_reduces_makespan(self):
        rng = np.random.default_rng(2)
        for _ in range(5):
            est = rng.random(8) + 0.05
            loads = np.full(8, 3)
            out = greedy_load_rebalance(est, loads, r_max=8)
            assert (est * out).max() <= (est * loads).max() + 1e-6

    @pytest.mark.parametrize("n,cap,min_load,pattern", [
        (3, 2, 1, "none"), (4, 3, 1, "some"), (6, 5, 2, "none"),
        (7, 8, 1, "all"), (9, 6, 2, "some"), (12, 4, 1, "dead_at_cap"),
        (16, 8, 1, "some"), (16, 8, 2, "dead_at_cap"), (5, 4, 1, "equal"),
        (10, 7, 2, "products"), (16, 8, 1, "products")],
        ids=lambda v: str(v))
    def test_closed_form_is_the_descent(self, n, cap, min_load, pattern):
        """The closed form equals the makespan descent bit for bit on every
        row without a finite tie at its threshold; where a row has one, the
        batch takes the descent, so the function equals it everywhere."""
        from repro.core.scheduling import (_rebalance_descent,
                                           _rebalance_fixed_point)
        rng = np.random.default_rng(n * 100 + cap * 10 + min_load)
        B = 48
        loads = rng.integers(min_load, cap + 1, n)      # uneven
        est = rng.uniform(0.05, 2.0, (B, n)).astype(np.float32)
        extra = int(loads.sum()) - n * min_load
        if pattern == "some":
            est[rng.random((B, n)) < 0.3] = np.inf
        elif pattern == "all":
            est[::2] = np.inf
            est[1::4, : n // 2] = np.inf
        elif pattern == "dead_at_cap":
            # the live workers' room (cap - min_load each) just below, at
            # and just above the total the dead ones must hand over
            live0 = extra // (cap - min_load)
            for b in range(B):
                live = int(np.clip(live0 + b % 3 - 1, 0, n))
                est[b, rng.permutation(n)[live:]] = np.inf
        elif pattern == "equal":
            est[:] = rng.uniform(0.05, 2.0, (B, 1))
        elif pattern == "products":
            # e_a * i == e_b * j for small whole slot numbers
            est = (rng.choice([2.0, 3.0, 4.0, 6.0], (B, n)) / 8
                   ).astype(np.float32)
        e = jnp.asarray(est)
        want = np.asarray(_rebalance_descent(e, loads, cap, min_load,
                                             n * cap))
        closed, tied = map(np.asarray, _rebalance_fixed_point(
            e, loads, cap, min_load))
        np.testing.assert_array_equal(closed[~tied], want[~tied])
        got = np.asarray(greedy_load_rebalance_batch(e, loads, r_max=cap,
                                                     min_load=min_load))
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int32 and (got.sum(-1) == loads.sum()).all()
        assert tied.any() == (pattern in ("equal", "products"))

    def test_tie_at_the_threshold_takes_the_descent(self):
        """Equal estimates on [1, 1, 3] under cap 4: the descent moves one
        slot from the last worker to the first, where the cheapest slots
        alone could give either of the first two."""
        from repro.core.scheduling import _rebalance_fixed_point
        e = jnp.ones((1, 3), jnp.float32)
        assert bool(_rebalance_fixed_point(e, np.array([1, 1, 3]), 4, 1)[1])
        got = greedy_load_rebalance_batch(e, [1, 1, 3], r_max=4)
        assert np.asarray(got).tolist() == [[2, 1, 2]]

    def test_truncated_descent_runs_alone(self):
        """``steps`` below ``n * (cap - min_load)`` stops the descent
        short of its fixed point, and the function returns that."""
        est = jnp.asarray([[1.0, 1.0, 1.0, 9.0]], jnp.float32)
        got = greedy_load_rebalance_batch(est, [2, 2, 2, 4], r_max=4,
                                          steps=1)
        assert np.asarray(got).tolist() == [[3, 2, 2, 3]]
        full = greedy_load_rebalance_batch(est, [2, 2, 2, 4], r_max=4)
        assert np.asarray(full).tolist() == [[3, 3, 3, 1]]

    def test_validation(self):
        with pytest.raises(ValueError, match="sum"):
            greedy_load_rebalance(np.ones(4), [2] * 4, total=9, r_max=4)
        with pytest.raises(ValueError, match="min_load"):
            greedy_load_rebalance(np.ones(4), [1] * 4, r_max=4, min_load=2)
        with pytest.raises(ValueError, match="r_max"):
            greedy_load_rebalance(np.ones(4), [5] * 4, r_max=4)
        with pytest.raises(ValueError, match="shape"):
            greedy_load_rebalance(np.ones(5), [2] * 4, r_max=4)
        out = greedy_load_rebalance(np.ones(4), total=9, r_max=4)
        assert out.sum() == 9                     # even split from total


# -------------------- (e) rounds axis with re-balancing ----------------------

class TestRebalanceRounds:
    def test_rebalance_chunk_invariant(self):
        n, k = 6, 5
        proc = MarkovRegimeProcess(base=scenario1(),
                                   worker_scale=heterogeneous_scales(n, 2.0),
                                   persistence=0.9)
        spec = adaptive_spec("rb", cyclic_to_matrix(n, 5), loads=[2] * n,
                             rebalance=True)
        for censored in (False, True):
            full = np.asarray(trajectory_samples(
                spec, proc, n, rounds=5, k=k, trials=300, seed=0,
                censored_feedback=censored))
            part = np.asarray(trajectory_samples(
                spec, proc, n, rounds=5, k=k, trials=300, seed=0, chunk=77,
                censored_feedback=censored))
            assert (full == part).all(), censored

    def test_rebalance_beats_permutation_only(self):
        """ISSUE-4 acceptance (small): at the same total budget, load
        re-balancing beats both static schedules AND the permutation-only
        adaptive scheme on a heterogeneous persistent cluster (paired
        samples, censored feedback)."""
        n, r, k = 10, 3, 8
        proc = ec2_cluster(n, spread=3.0, p_slow=0.25, persistence=0.95,
                           slow=8.0)
        cs = cyclic_to_matrix(n, r)
        specs = [to_spec("cs", cs),
                 to_spec("ss", staircase_to_matrix(n, r)),
                 adaptive_spec("adapt", cs),
                 adaptive_spec("rebal", cyclic_to_matrix(n, 6),
                               loads=[r] * n, rebalance=True)]
        res = sweep_rounds(specs, proc, n, rounds=16, k=k, trials=800,
                           seed=0, censored_feedback=True)
        rebal = res.mean_round("rebal")
        assert rebal < res.mean_round("cs")
        assert rebal < res.mean_round("ss")
        assert rebal < res.mean_round("adapt")

    def test_static_ragged_adaptive_chunk_invariant(self):
        n = 6
        proc = MarkovRegimeProcess(base=scenario1(),
                                   worker_scale=heterogeneous_scales(n, 2.0),
                                   persistence=0.9)
        spec = adaptive_spec("ar", staircase_to_matrix(n, loads=LOADS),
                             messages=2)
        full = np.asarray(trajectory_samples(spec, proc, n, rounds=4, k=4,
                                             trials=240, seed=0,
                                             censored_feedback=True))
        part = np.asarray(trajectory_samples(spec, proc, n, rounds=4, k=4,
                                             trials=240, seed=0, chunk=77,
                                             censored_feedback=True))
        assert (full == part).all()

    def test_rebalance_spec_validation(self):
        C = cyclic_to_matrix(6, 4)
        m = scenario1()
        with pytest.raises(ValueError, match="budget"):
            sweep_rounds([adaptive_spec("a", C, rebalance=True)], m, 6,
                         rounds=2, k=3, trials=8)
        with pytest.raises(ValueError, match="dense"):
            sweep_rounds([adaptive_spec(
                "a", staircase_to_matrix(6, loads=LOADS), loads=LOADS,
                rebalance=True)], m, 6, rounds=2, k=3, trials=8)
        from repro.core.scheduling import block_to_matrix
        with pytest.raises(ValueError, match="diagonal"):
            sweep_rounds([adaptive_spec("a", block_to_matrix(6, 4),
                                        loads=[2] * 6, rebalance=True)],
                         m, 6, rounds=2, k=3, trials=8)

    def test_scheduler_rebalance_state(self):
        sched = AdaptiveScheduler(cyclic_to_matrix(6, 6), loads=[3] * 6,
                                  rebalance=True)
        assert (sched.loads() == 3).all()          # no feedback yet
        sched.observe(np.array([1, 1, 9, 1, 9, 1.0]))
        loads = sched.loads()
        assert loads.sum() == 18 and loads[2] == 1 and loads[4] == 1
        M = sched.matrix()
        assert (loads_of_matrix(M) == loads).all()
        validate_to_matrix(M, 6)


# ------------------- (f) aggregator / train API ragged rounds ----------------

class TestRaggedAggregator:
    def test_ragged_round_weights(self):
        spec = RoundSpec(n=6, r=3, k=4, schedule="ss", loads=LOADS)
        agg = StragglerAggregator(spec, scenario1())
        C = agg.current_matrix()
        assert (loads_of_matrix(C) == np.asarray(LOADS)).all()
        w, t = agg.round_mask(jax.random.PRNGKey(0))
        w = np.asarray(w)
        assert np.isclose(w.sum(), 4.0, atol=1e-5)
        assert (w[C == MASKED] == 0).all()
        out = agg.combine({"g": jnp.ones((6, 3, 2))}, jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(out["g"]), 1.0, rtol=1e-5)

    def test_rebalance_round_api(self):
        spec = RoundSpec(n=8, r=5, k=6, schedule="cs", loads=(2,) * 8)
        proc = ec2_cluster(8, spread=3.0, persistence=0.95, slow=10.0)
        agg = StragglerAggregator(spec, proc, adaptive=True,
                                  censored_feedback=True, rebalance=True)
        for i in range(4):
            C = agg.current_matrix()
            validate_to_matrix(C, 8)
            lv = agg.current_loads()
            assert lv.sum() == 16 and (loads_of_matrix(C) == lv).all()
            w, t = agg.round_mask(jax.random.PRNGKey(i))
            assert np.isclose(float(np.asarray(w).sum()), 6.0, atol=1e-4)
        assert agg.expected_completion(trials=256) > 0

    def test_rebalance_requires_adaptive_and_budget(self):
        m = scenario1()
        with pytest.raises(ValueError, match="adaptive"):
            StragglerAggregator(RoundSpec(n=4, r=2, k=3, loads=(1,) * 4), m,
                                rebalance=True)
        with pytest.raises(ValueError, match="budget"):
            StragglerAggregator(RoundSpec(n=4, r=2, k=3), m, adaptive=True,
                                rebalance=True)

    def test_roundspec_loads_validation(self):
        with pytest.raises(ValueError, match="loads"):
            RoundSpec(n=4, r=2, k=3, loads=(3, 1, 1, 1))   # load > r
        with pytest.raises(ValueError, match="diagonal"):
            RoundSpec(n=4, r=2, k=3, schedule="block", loads=(2, 1, 1, 2))
        spec = RoundSpec(n=4, r=2, k=3, schedule="cs", loads=[2, 1, 1, 2])
        assert spec.loads == (2, 1, 1, 2)                  # canonical tuple
        assert (spec.load_vector == [2, 1, 1, 2]).all()


# ----------------- (g) per-message overhead (comm_eps) -----------------------

class TestCommOverhead:
    def test_engine_matches_numpy_oracle(self):
        n, r, k, trials, eps = 7, 3, 5, 200, 2e-4
        model = ShiftedExponentialDelays()
        C = cyclic_to_matrix(n, r)
        T1, T2 = _oracle_draws(model, n, r, trials, seed=11)
        s = np.cumsum(T1, -1) + T2
        for messages in (1, 2, 3):
            b = message_boundaries(r, messages)
            msgidx = np.searchsorted(b, np.arange(r))
            sm = s[..., b[msgidx]] + eps * (msgidx + 1)
            tau = np.full((trials, n), np.inf)
            for w in range(n):
                for j in range(r):
                    tau[:, C[w, j]] = np.minimum(tau[:, C[w, j]],
                                                 sm[:, w, j])
            got = np.asarray(completion_samples(
                to_spec("x", C, messages=messages, comm_eps=eps), model, n,
                trials=trials, seed=11, k=k))
            np.testing.assert_allclose(got, np.sort(tau, -1)[:, k - 1],
                                       rtol=1e-6)

    def test_zero_eps_bitexact_and_monotone(self):
        n, r, k = 8, 4, 7
        m = scenario1()
        C = cyclic_to_matrix(n, r)
        a = completion_samples(to_spec("x", C), m, n, trials=400, seed=2,
                               k=k)
        b = completion_samples(to_spec("x", C, comm_eps=0.0), m, n,
                               trials=400, seed=2, k=k)
        assert (np.asarray(a) == np.asarray(b)).all()
        # paired draws: completion is nondecreasing in eps
        specs = [to_spec(f"e{i}", C, comm_eps=eps)
                 for i, eps in enumerate((0.0, 1e-4, 5e-4))]
        res = sweep(specs, m, n, trials=2000, seed=0, ks=k)
        t = [res.at_k(f"e{i}", k) for i in range(3)]
        assert t[0] < t[1] < t[2]

    def test_message_comm_delays_overhead(self):
        m = scenario1()
        T1, T2 = m.sample(jax.random.PRNGKey(0), 4, 5, 4)
        base = np.asarray(message_comm_delays(T2, 2))
        got = np.asarray(message_comm_delays(T2, 2, eps=1e-3))
        np.testing.assert_allclose(got - base,
                                   np.broadcast_to([1e-3, 2e-3], base.shape),
                                   rtol=1e-5)
        # identity budget + eps still applies the overhead
        got4 = np.asarray(message_comm_delays(T2, 4, eps=1e-3))
        np.testing.assert_allclose(
            got4 - np.asarray(T2),
            np.broadcast_to([1e-3, 2e-3, 3e-3, 4e-3], np.asarray(T2).shape),
            rtol=1e-4)

    def test_overhead_flips_optimal_budget(self):
        """The Ozfatura trade-off: with zero overhead m=r wins; with a
        large overhead one-shot wins (k=n on a straggling cluster)."""
        from repro.core import BimodalStragglerDelays
        n, r, k = 10, 4, 9
        model = BimodalStragglerDelays(p_straggle=0.25, slow=8.0)
        C = cyclic_to_matrix(n, r)
        specs = []
        for tag, eps in (("lo", 0.0), ("hi", 1.5e-3)):
            specs += [to_spec(f"{tag}_m{mm}", C, messages=mm, comm_eps=eps)
                      for mm in (1, r)]
        res = sweep(specs, model, n, trials=4000, seed=0, ks=k)
        assert res.at_k(f"lo_m{r}", k) < res.at_k("lo_m1", k)
        assert res.at_k("hi_m1", k) < res.at_k(f"hi_m{r}", k)


# ------------------------------ misc / exports -------------------------------

def test_message_budget_validation_messages():
    with pytest.raises(ValueError, match="messages"):
        message_boundaries(4, 0)
    with pytest.raises(ValueError, match="messages"):
        message_boundaries(4, 5)
    with pytest.raises(ValueError, match="integer"):
        message_boundaries(4, 2.5)
    with pytest.raises(ValueError, match="messages"):
        message_group_sizes(3, 4)


def test_clear_cache_exported_and_callable():
    clear_cache()          # drops compiled evaluators; next sweep recompiles
    m = scenario1()
    res = sweep([to_spec("x", cyclic_to_matrix(4, 2))], m, 4, trials=16,
                ks=2)
    assert res.at_k("x", 2) > 0


def test_ragged_spec_constructors_reject_coded_loads():
    from repro.core import SchemeSpec
    m = scenario1()
    with pytest.raises(ValueError, match="coded"):
        sweep([SchemeSpec(name="p", kind="pc", r=2, loads=(1, 2, 2, 1))],
              m, 4, trials=8)
