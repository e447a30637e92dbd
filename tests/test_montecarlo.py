"""Tests for the fused Monte-Carlo sweep engine (core/montecarlo.py).

Covers the ISSUE-1 acceptance points:
  (a) engine results bit-match the public simulate_* wrappers per scheme;
  (b) chunked streaming equals unchunked (per-trial subkeys make the draws
      chunking-invariant);
  (c) the all-k output column k equals the single-k (rank count) path;
  (d) the static gather task-arrival layout equals the scatter-min version
      on random TO matrices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import montecarlo as mc
from repro.core import (cyclic_to_matrix, staircase_to_matrix,
                        random_assignment_to_matrix, scenario1, ec2_like,
                        ShiftedExponentialDelays, slot_arrival_times,
                        task_arrival_times, pc_threshold, pcmm_threshold,
                        simulate_completion, simulate_lower_bound,
                        simulate_pc_completion, simulate_pcmm_completion,
                        mean_completion_time, to_spec, lb_spec, pc_spec,
                        pcmm_spec, tau_spec, adaptive_spec, sweep,
                        sweep_rounds, completion_samples,
                        trajectory_samples, task_arrival_samples,
                        task_gather_plan, task_arrival_times_gather,
                        ec2_cluster, IIDProcess)


def _random_to_matrix(n, r, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(n)[:r] for _ in range(n)])


# ---------------------------- (a) bit-match ----------------------------------

def test_engine_bitmatches_simulate_completion():
    n, r, k, trials = 8, 4, 6, 2000
    m = scenario1()
    C = staircase_to_matrix(n, r)
    wrapper = np.asarray(simulate_completion(C, m, k, trials=trials, seed=3))
    engine = np.asarray(completion_samples(to_spec("x", C), m, n,
                                           trials=trials, seed=3, k=k))
    assert (wrapper == engine).all()


def test_engine_bitmatches_simulate_lower_bound():
    n, r, k, trials = 8, 3, 5, 2000
    m = scenario1()
    wrapper = np.asarray(simulate_lower_bound(m, n, r, k, trials=trials,
                                              seed=7))
    engine = np.asarray(completion_samples(lb_spec(r), m, n, trials=trials,
                                           seed=7, k=k))
    assert (wrapper == engine).all()


def test_engine_bitmatches_coded_simulators():
    n, r, trials = 8, 4, 2000
    m = scenario1()
    pc = np.asarray(simulate_pc_completion(m, n, r, trials=trials, seed=1))
    pc_eng = np.asarray(completion_samples(pc_spec(r), m, n, trials=trials,
                                           seed=1))
    assert (pc == pc_eng).all()
    pcmm = np.asarray(simulate_pcmm_completion(m, n, r, trials=trials, seed=1))
    pcmm_eng = np.asarray(completion_samples(pcmm_spec(r), m, n,
                                             trials=trials, seed=1))
    assert (pcmm == pcmm_eng).all()


def test_engine_matches_independent_oracle():
    """The engine against a from-scratch oracle sharing only the per-trial
    key convention: batch-sampled draws, scatter-min arrivals (the seed
    implementation), a plain numpy sort — none of the engine's gather /
    top_k / scan machinery.  Guards against wrapper-vs-engine tautology."""
    n, r, k, trials = 7, 3, 5, 300
    m = ShiftedExponentialDelays()
    C = cyclic_to_matrix(n, r)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(11), jnp.arange(trials, dtype=jnp.int32))
    taus = []
    for i in range(trials):                       # deliberately unvectorized
        T1, T2 = m.sample(keys[i], 1, n, r)
        s = np.asarray(slot_arrival_times(T1, T2))[0]
        tau = np.full(n, np.inf)
        for w in range(n):
            for j in range(r):
                tau[C[w, j]] = min(tau[C[w, j]], s[w, j])
        taus.append(np.sort(tau))
    oracle = np.stack(taus)                       # (trials, n), all k
    engine = np.asarray(completion_samples(to_spec("x", C), m, n,
                                           trials=trials, seed=11))
    np.testing.assert_allclose(engine, oracle, rtol=1e-6)
    # order statistics: k-th column is the k-th smallest
    single = np.asarray(completion_samples(to_spec("x", C), m, n,
                                           trials=trials, seed=11, k=k))
    np.testing.assert_allclose(single, oracle[:, k - 1], rtol=1e-6)


def test_sweep_mean_matches_sample_mean():
    n, r, k, trials = 8, 4, 6, 3000
    m = ec2_like(n, seed=5)
    C = cyclic_to_matrix(n, r)
    res = sweep([to_spec("cs", C)], m, n, trials=trials, seed=0)
    samples = np.asarray(simulate_completion(C, m, k, trials=trials, seed=0))
    assert np.isclose(res.at_k("cs", k), samples.mean(), rtol=1e-5)
    assert np.isclose(mean_completion_time(C, m, k, trials=trials, seed=0),
                      samples.mean(), rtol=1e-5)


# ------------------------- (b) chunked == unchunked --------------------------

@pytest.mark.parametrize("chunk", [1, 7, 250, 1000])
def test_chunked_samples_equal_unchunked(chunk):
    n, r, k, trials = 6, 3, 4, 1000
    m = scenario1()
    C = cyclic_to_matrix(n, r)
    full = np.asarray(completion_samples(to_spec("x", C), m, n,
                                         trials=trials, seed=0, k=k))
    part = np.asarray(completion_samples(to_spec("x", C), m, n,
                                         trials=trials, seed=0, k=k,
                                         chunk=chunk))
    assert (full == part).all()


@pytest.mark.parametrize("chunk", [7, 250])
def test_chunked_samples_equal_unchunked_vector_mean(chunk):
    # per-worker means (ec2_like): the truncation bounds stay scalars, so
    # no draw depends on how the trial axis is cut
    n, r, k, trials = 6, 3, 4, 1000
    m = ec2_like(n)
    C = cyclic_to_matrix(n, r)
    full = np.asarray(completion_samples(to_spec("x", C), m, n,
                                         trials=trials, seed=0, k=k))
    part = np.asarray(completion_samples(to_spec("x", C), m, n,
                                         trials=trials, seed=0, k=k,
                                         chunk=chunk))
    np.testing.assert_array_equal(full, part)


def test_chunked_sweep_means_equal_unchunked():
    n, r, trials = 6, 6, 2000
    m = scenario1()
    specs = [to_spec("cs", cyclic_to_matrix(n, r)),
             pc_spec(r), pcmm_spec(r), lb_spec(r)]
    full = sweep(specs, m, n, trials=trials, seed=0)
    part = sweep(specs, m, n, trials=trials, seed=0, chunk=300)
    for name in full.means:
        np.testing.assert_allclose(part.means[name], full.means[name],
                                   rtol=1e-5)


def test_chunked_large_sweep_streams():
    """A trial count far above any single-batch memory budget must still
    run (O(chunk) memory) and agree statistically with a small sweep."""
    n, r, k = 6, 3, 5
    m = scenario1()
    specs = [to_spec("cs", cyclic_to_matrix(n, r))]
    big = sweep(specs, m, n, trials=60000, seed=0, chunk=4096)
    small = sweep(specs, m, n, trials=10000, seed=1)
    assert abs(big.at_k("cs", k) - small.at_k("cs", k)) < 5e-5


# ---------------------- (c) all-k column == single-k -------------------------

@pytest.mark.parametrize("k", [1, 3, 6, 8])
def test_all_k_column_equals_single_k(k):
    n, r, trials = 8, 4, 1500
    m = scenario1()
    C = staircase_to_matrix(n, r)
    allk = np.asarray(completion_samples(to_spec("x", C), m, n,
                                         trials=trials, seed=2))
    single = np.asarray(completion_samples(to_spec("x", C), m, n,
                                           trials=trials, seed=2, k=k))
    assert allk.shape == (trials, n)
    assert (allk[:, k - 1] == single).all()


def test_all_k_columns_nondecreasing():
    n, r = 8, 8
    m = scenario1()
    res = sweep([to_spec("ss", staircase_to_matrix(n, r)), lb_spec(r)], m, n,
                trials=2000, seed=0)
    for name in ("ss", "lb"):
        assert (np.diff(res.means[name]) >= -1e-9).all()
    # lower bound dominates the schedule at every k
    assert (res.means["lb"] <= res.means["ss"] + 1e-9).all()


# ----------------------- (d) gather == scatter-min ---------------------------

@pytest.mark.parametrize("seed", range(6))
def test_gather_plan_matches_scatter_min(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 10))
    r = int(rng.integers(1, n + 1))
    C = _random_to_matrix(n, r, seed)
    m = ShiftedExponentialDelays()
    T1, T2 = m.sample(jax.random.PRNGKey(seed), 32, n, r)
    s = slot_arrival_times(T1, T2)
    scatter = np.asarray(task_arrival_times(jnp.asarray(C), s, n))
    gather = np.asarray(task_arrival_times_gather(task_gather_plan(C, n), s))
    assert np.array_equal(scatter, gather)   # inf-padded tasks included


def test_gather_plan_handles_unassigned_tasks():
    C = np.array([[0], [0]])                 # task 1 never computed
    plan = task_gather_plan(C, 2)
    s = jnp.ones((1, 2, 1))
    tau = np.asarray(task_arrival_times_gather(plan, s))
    assert np.isinf(tau[0, 1]) and tau[0, 0] == 1.0


def test_gather_plan_wide_slot_grid():
    """Schemes with r < r_max read the leading slots of the shared grid."""
    n, r, r_max = 6, 2, 5
    C = cyclic_to_matrix(n, r)
    m = scenario1()
    T1, T2 = m.sample(jax.random.PRNGKey(0), 16, n, r_max)
    s = slot_arrival_times(T1, T2)
    gather = np.asarray(task_arrival_times_gather(
        task_gather_plan(C, n, r_max), s))
    scatter = np.asarray(task_arrival_times(jnp.asarray(C), s[..., :r], n))
    assert np.array_equal(scatter, gather)


# ------------------------------ misc engine ----------------------------------

def test_common_random_numbers_pair_schemes():
    """CS and SS evaluated under one seed share delay draws: the
    per-trial gap estimator has lower variance than with independent
    draws (the CRN payoff).  Compared at the trial level — 800 paired
    samples — so the check measures the true variance reduction rather
    than a handful of noisy seed-level std estimates."""
    n, r, k, trials = 10, 5, 8, 800
    m = scenario1()
    cs_s = to_spec("cs", cyclic_to_matrix(n, r))
    ss_s = to_spec("ss", staircase_to_matrix(n, r))
    cs0 = np.asarray(completion_samples(cs_s, m, n, trials=trials,
                                        seed=0, k=k)).ravel()
    ss0 = np.asarray(completion_samples(ss_s, m, n, trials=trials,
                                        seed=0, k=k)).ravel()
    ss1 = np.asarray(completion_samples(ss_s, m, n, trials=trials,
                                        seed=1, k=k)).ravel()
    # shared draws -> strongly correlated completions
    assert np.corrcoef(cs0, ss0)[0, 1] > 0.5
    # ... so the paired gap has materially lower variance than the
    # same estimator built from independent draws
    assert np.std(cs0 - ss0) < 0.8 * np.std(cs0 - ss1)


def test_task_arrival_samples_shape_and_consistency():
    n, r, trials = 6, 3, 500
    m = scenario1()
    C = cyclic_to_matrix(n, r)
    tau = np.asarray(task_arrival_samples(C, m, trials=trials, seed=0))
    assert tau.shape == (trials, n)
    # k-th order statistic of tau == engine completion samples
    allk = np.asarray(completion_samples(to_spec("x", C), m, n,
                                         trials=trials, seed=0))
    assert np.allclose(np.sort(tau, axis=1), allk)


def test_sweep_rejects_bad_input():
    m = scenario1()
    C = cyclic_to_matrix(4, 2)
    with pytest.raises(ValueError):
        sweep([to_spec("a", C), to_spec("a", C)], m, 4, trials=8)
    with pytest.raises(ValueError):
        sweep([to_spec("a", C)], m, 5, trials=8)          # row/task mismatch
    with pytest.raises(ValueError):
        sweep([to_spec("a", C)], m, 4, trials=8, ks=9)    # k out of range
    res = sweep([to_spec("a", C)], m, 4, trials=8, ks=2)
    with pytest.raises(ValueError):
        res.at_k("a", 3)                                  # wrong k for ks=2
    with pytest.raises(ValueError):
        sweep([pcmm_spec(1)], m, 4, trials=8)             # n*r < 2n-1


def test_at_k_edge_cases():
    """SweepResult.at_k: the single-k (rank count) path and the all-k (full
    sort) path agree at every k on shared draws; unknown names raise."""
    n, r, trials = 8, 4, 800
    m = scenario1()
    specs = [to_spec("cs", cyclic_to_matrix(n, r)), lb_spec(r)]
    allk = sweep(specs, m, n, trials=trials, seed=4)
    for k in range(1, n + 1):
        single = sweep(specs, m, n, trials=trials, seed=4, ks=k)
        for name in ("cs", "lb"):
            assert np.isclose(allk.at_k(name, k), single.at_k(name, k),
                              rtol=1e-6), (name, k)
    with pytest.raises(ValueError, match="unknown scheme"):
        allk.at_k("nope", 3)
    with pytest.raises(ValueError):
        allk.at_k("cs")                          # all-k needs explicit k
    with pytest.raises(ValueError):
        allk.at_k("cs", 0)                       # out of range


# ----------------------------- rounds axis -----------------------------------

def test_sweep_rounds_validation():
    n, r = 6, 3
    m = scenario1()
    C = cyclic_to_matrix(n, r)
    with pytest.raises(ValueError, match="rounds axis"):
        sweep([adaptive_spec("a", C)], m, n, trials=8)
    with pytest.raises(ValueError, match="single-round"):
        sweep_rounds([tau_spec("t", C)], m, n, rounds=2, k=3, trials=8)
    with pytest.raises(ValueError):
        sweep_rounds([to_spec("a", C)], m, n, rounds=0, k=3, trials=8)
    with pytest.raises(ValueError):
        sweep_rounds([to_spec("a", C)], m, n, rounds=2, k=9, trials=8)
    res = sweep_rounds([to_spec("a", C)], m, n, rounds=2, k=3, trials=64)
    with pytest.raises(ValueError, match="unknown scheme"):
        res.mean_round("nope")


def test_rounds_trajectories_chunk_invariant_and_consistent():
    n, r, k, trials, rounds = 6, 3, 5, 400, 5
    # per-trial draws are bit-identical under any chunking (vector-mean
    # bases too: test_ec2_cluster_chunking_close below).
    from repro.core import MarkovRegimeProcess, heterogeneous_scales
    proc = MarkovRegimeProcess(base=scenario1(),
                               worker_scale=heterogeneous_scales(n, 2.0),
                               persistence=0.9)
    spec = to_spec("cs", cyclic_to_matrix(n, r))
    full = np.asarray(trajectory_samples(spec, proc, n, rounds=rounds, k=k,
                                         trials=trials, seed=0))
    part = np.asarray(trajectory_samples(spec, proc, n, rounds=rounds, k=k,
                                         trials=trials, seed=0, chunk=77))
    assert full.shape == (trials, rounds)
    assert (full == part).all()
    # sweep_rounds moments match the raw trajectories
    res = sweep_rounds([spec], proc, n, rounds=rounds, k=k, trials=trials,
                       seed=0, chunk=128)
    np.testing.assert_allclose(res.per_round["cs"], full.mean(0), rtol=1e-5)
    np.testing.assert_allclose(res.wallclock["cs"],
                               np.cumsum(full, axis=1).mean(0), rtol=1e-5)
    np.testing.assert_allclose(res.wallclock["cs"],
                               np.cumsum(res.per_round["cs"]), rtol=1e-5)
    assert res.total("cs") > res.mean_round("cs") > 0


def test_ec2_cluster_chunking_close():
    """Vector-mean bases (ec2_like) are chunk-invariant bit for bit, also
    between one chunk per scan and several."""
    n, r, k = 6, 3, 5
    proc = ec2_cluster(n, spread=2.0, persistence=0.9)
    spec = to_spec("cs", cyclic_to_matrix(n, r))
    full = np.asarray(trajectory_samples(spec, proc, n, rounds=4, k=k,
                                         trials=300, seed=0))
    part = np.asarray(trajectory_samples(spec, proc, n, rounds=4, k=k,
                                         trials=300, seed=0, chunk=77))
    np.testing.assert_array_equal(part, full)
    one = np.asarray(trajectory_samples(spec, proc, n, rounds=4, k=k,
                                        trials=77, seed=0, chunk=77))
    np.testing.assert_array_equal(one, full[:77])


def test_adaptive_beats_static_on_persistent_heterogeneous_cluster():
    """ISSUE-2 acceptance: with worker-specific persistent straggling, the
    feedback-driven row re-assignment beats BOTH static schedules' mean
    wall-clock per round (paired comparison — shared realizations)."""
    n, r, k = 10, 3, 8
    proc = ec2_cluster(n, spread=3.0, p_slow=0.25, persistence=0.95,
                       slow=8.0)
    cs = cyclic_to_matrix(n, r)
    res = sweep_rounds([to_spec("cs", cs),
                        to_spec("ss", staircase_to_matrix(n, r)),
                        adaptive_spec("adapt", cs), lb_spec(r)],
                       proc, n, rounds=16, k=k, trials=1200, seed=0)
    adapt = res.mean_round("adapt")
    assert adapt < res.mean_round("cs")
    assert adapt < res.mean_round("ss")
    assert res.mean_round("lb") < adapt          # oracle still dominates
    # the adaptive edge needs feedback: round 0 (no history) is not better
    # than cs beyond noise, later rounds are.
    gap0 = res.per_round["cs"][0] - res.per_round["adapt"][0]
    gap_late = (res.per_round["cs"][-4:] - res.per_round["adapt"][-4:]).mean()
    assert gap_late > gap0


def test_pc_keeps_own_threshold_in_single_k_sweeps():
    """Coded schemes are never scored at the sweep's k: a single-k sweep
    reports pc at 2*ceil(n/r)-1 regardless of ks."""
    n, r, k = 8, 4, 2
    m = scenario1()
    allk = sweep([pc_spec(r)], m, n, trials=500, seed=0)
    single = sweep([pc_spec(r), to_spec("cs", cyclic_to_matrix(n, r))], m, n,
                   trials=500, seed=0, ks=k)
    assert single.at_k("pc") == allk.at_k("pc")           # k-independent
    assert pc_threshold(n, r) != k                        # and != sweep's k


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("L", [1, 5, 16, mc._RANK_COUNT_MAX_WIDTH + 1])
def test_kth_smallest_equals_sort(L, traced):
    """The k-th order statistic, by rank count up to the width cutoff and
    by top_k / sort above it, is bit for bit ``np.sort(x)[..., k-1]`` at
    every k: ties, +inf sentinels and all-+inf rows included, with k a
    Python int or a traced array under jit."""
    rng = np.random.default_rng(L)
    x = np.round(rng.uniform(size=(4, 6, L)) * 8) / 8       # many ties
    x = x.astype(np.float32)
    x[rng.uniform(size=x.shape) < 0.3] = np.inf
    x[0, 0] = np.inf                                        # all +inf
    x[1, 2] = x[1, 2, :1]                                   # all tied
    ref = np.sort(x, axis=-1)
    jk = jax.jit(mc._kth_smallest)
    for k in range(1, L + 1):
        got = (jk(x, jnp.full((4, 6, 1), k, jnp.int32)) if traced
               else mc._kth_smallest(jnp.asarray(x), k))
        assert got.shape == (4, 6, 1)
        np.testing.assert_array_equal(np.asarray(got), ref[..., k - 1:k])
