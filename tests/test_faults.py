"""Fault injection and graceful degradation (ISSUE-6).

Covers the acceptance points:
  (a) the ``FaultProcess`` scenario zoo composes over any base delay
      source and injects +inf ("never arrives") / load swell where the
      scenario says, never NaN;
  (b) engine edge cases: every worker dead, a single survivor at k=1,
      a deadline below every arrival — all close finitely under the
      closing policies with sane degradation metrics;
  (c) the ``reissue`` policy is chunk-invariant under common random
      numbers (per-trial trajectories bit-exact across chunk sizes);
  (d) property: a fault-bearing recording replays bit-exactly through
      ``sweep_rounds`` — per-round times AND degradation streams — for
      every zoo scenario and closing policy (the v2 +inf trace format
      round-trips through disk on the way);
  (e) crash-aware scheduling: dead-worker detection, coverage repair,
      and the clear error when graceful degradation is impossible;
  (f) spec-level guards: impossible coverage and deadline-policy
      validation fail fast with explicit messages.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (MASKED, AdaptiveScheduler, DelayTrace,
                        FAULT_SCENARIOS, IIDProcess, RoundConfig,
                        TraceProcess, adaptive_spec, cyclic_to_matrix,
                        load_trace, make_scenario, save_trace, scenario1,
                        sweep_rounds, to_spec, trajectory_samples,
                        validate_trace_file)
import repro.core.trace as trace_mod

N, R, K, ROUNDS, TRIALS = 6, 2, 3, 5, 48
DEADLINE = 2e-3           # ~2x scenario1's typical round, well above arrivals
SCHEMES = ("cs", "ad")


def _specs():
    return [to_spec("cs", cyclic_to_matrix(N, R)),
            adaptive_spec("ad", cyclic_to_matrix(N, R))]


def _sweep(process, *, k=K, deadline=None, policy="wait", chunk=16,
           record=False, specs=None):
    return sweep_rounds(specs or _specs(), process, N, rounds=ROUNDS, k=k,
                        trials=TRIALS, seed=0, chunk=chunk,
                        censored_feedback=True, record_trace=record,
                        deadline=deadline, deadline_policy=policy)


# --------------------------- (a) the scenario zoo ----------------------------

def test_scenario_zoo_constructs_and_injects_cleanly():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    for name in FAULT_SCENARIOS:
        proc = make_scenario(name, scenario1(), N)
        state = proc.init(keys, N)
        for _ in range(3):
            state, T1, T2 = proc.step(state, keys, N, R)
            for T in (np.asarray(T1), np.asarray(T2)):
                assert not np.isnan(T).any()
                assert (T[np.isfinite(T)] > 0).all()
    with pytest.raises(ValueError, match="unknown fault scenario"):
        make_scenario("meteor", scenario1(), N)


def test_partition_window_is_deterministic():
    proc = make_scenario("partition", scenario1(), N, workers=(0, 1),
                         start=1, length=1)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    state = proc.init(keys, N)
    cut_per_round = []
    for _ in range(3):
        state, T1, T2 = proc.step(state, keys, N, R)
        assert np.isfinite(np.asarray(T1)).all()   # compute keeps running
        cut_per_round.append(np.isinf(np.asarray(T2)))
    assert not cut_per_round[0].any() and not cut_per_round[2].any()
    assert cut_per_round[1][:, :2].all() and not cut_per_round[1][:, 2:].any()


def test_diurnal_swells_but_never_censors():
    proc = make_scenario("diurnal", scenario1(), N, period=4, amplitude=3.0)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    state = proc.init(keys, N)
    means = []
    for _ in range(3):
        state, T1, T2 = proc.step(state, keys, N, R)
        assert np.isfinite(np.asarray(T1)).all()
        assert np.isfinite(np.asarray(T2)).all()
        means.append(float(np.mean(np.asarray(T1))))
    assert means[2] > means[0]        # round 2 sits near the swell peak


# ------------------------- (b) engine edge cases -----------------------------

def test_all_workers_dead_closes_partial_rounds():
    proc = make_scenario("preemption", scenario1(), N, kill_p=1.0,
                         respawn_p=0.0)
    res = _sweep(proc, deadline=DEADLINE, policy="close_partial")
    for nm in SCHEMES:
        pr = np.asarray(res.per_round[nm])
        assert np.isfinite(pr).all() and (pr <= DEADLINE * (1 + 1e-6)).all()
        assert np.allclose(res.realized_k(nm), 0.0)
        assert np.allclose(res.missed_fraction(nm), 1.0)
        assert np.allclose(res.khist(nm)[:, 0], 1.0)
    # the wait policy reports the truth — +inf, never NaN — and still
    # flags every round as missed
    res_w = _sweep(proc, deadline=DEADLINE, policy="wait")
    for nm in SCHEMES:
        pr = np.asarray(res_w.per_round[nm])
        assert np.isinf(pr).all() and not np.isnan(pr).any()
        assert np.allclose(res_w.missed_fraction(nm), 1.0)


def test_single_survivor_completes_k1():
    proc = make_scenario("partition", scenario1(), N,
                         workers=tuple(range(N - 1)), start=0, length=ROUNDS)
    res = _sweep(proc, k=1, specs=[to_spec("cs", cyclic_to_matrix(N, R))])
    assert np.isfinite(np.asarray(res.per_round["cs"])).all()
    # ... and k beyond the survivor's rows never completes under wait
    res2 = _sweep(proc, k=K, specs=[to_spec("cs", cyclic_to_matrix(N, R))])
    assert np.isinf(np.asarray(res2.per_round["cs"])).all()


def test_deadline_below_every_arrival():
    dl = 1e-9
    res = _sweep(IIDProcess(scenario1()), deadline=dl, policy="close_partial")
    for nm in SCHEMES:
        assert np.allclose(res.per_round[nm], dl)
        assert np.allclose(res.realized_k(nm), 0.0)
        assert np.allclose(res.missed_fraction(nm), 1.0)
        assert np.allclose(res.stale_fraction(nm), 1.0)


def test_khist_is_a_distribution_over_realized_k():
    res = _sweep(make_scenario("preemption", scenario1(), N),
                 deadline=DEADLINE, policy="close_partial")
    for nm in SCHEMES:
        hist = res.khist(nm)
        assert hist.shape == (ROUNDS, K + 1)
        assert np.allclose(hist.sum(axis=1), 1.0, atol=1e-5)
        mean_from_hist = hist @ np.arange(K + 1)
        assert np.allclose(mean_from_hist, res.realized_k(nm), atol=1e-4)


def test_degradation_requires_a_deadline():
    res = _sweep(IIDProcess(scenario1()))
    assert res.degradation is None
    with pytest.raises(ValueError, match="deadline"):
        res.realized_k("cs")


# --------------------- (c) reissue chunk invariance (CRN) --------------------

def test_reissue_chunk_invariant_under_crn():
    proc = make_scenario("preemption", scenario1(), N)
    sp = adaptive_spec("ad", cyclic_to_matrix(N, R))
    a = trajectory_samples(sp, proc, N, rounds=ROUNDS, k=K, trials=TRIALS,
                           seed=0, chunk=16, censored_feedback=True,
                           deadline=DEADLINE, deadline_policy="reissue")
    b = trajectory_samples(sp, proc, N, rounds=ROUNDS, k=K, trials=TRIALS,
                           seed=0, chunk=7, censored_feedback=True,
                           deadline=DEADLINE, deadline_policy="reissue")
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # aggregate streams agree across chunkings too
    ra = _sweep(proc, deadline=DEADLINE, policy="reissue", chunk=16)
    rb = _sweep(proc, deadline=DEADLINE, policy="reissue", chunk=7)
    for nm in SCHEMES:
        assert np.allclose(ra.per_round[nm], rb.per_round[nm], rtol=1e-6)
        for key in ("realized_k", "missed", "stale", "khist"):
            assert np.allclose(ra.degradation[nm][key],
                               rb.degradation[nm][key], atol=1e-6)


# ------------------ (d) fault-bearing trace replay property ------------------

@settings(deadline=None, max_examples=10)
@given(st.sampled_from(FAULT_SCENARIOS),
       st.sampled_from(("close_partial", "reissue")))
def test_fault_trace_replay_bit_exact(scenario, policy):
    """The acceptance criterion: a recording made under any zoo scenario
    and closing policy replays bit-exactly — identical per-round times
    and identical degradation streams — after a disk round-trip."""
    proc = make_scenario(scenario, scenario1(), N)
    res = _sweep(proc, deadline=DEADLINE, policy=policy, record=True)
    rep = _sweep(TraceProcess(res.trace), deadline=DEADLINE, policy=policy)
    for nm in SCHEMES:
        assert np.array_equal(res.per_round[nm], rep.per_round[nm])
        for key in ("realized_k", "missed", "stale", "khist"):
            assert np.array_equal(res.degradation[nm][key],
                                  rep.degradation[nm][key])


def test_fault_trace_disk_roundtrip_v2(tmp_path):
    res = _sweep(make_scenario("preemption", scenario1(), N,
                               kill_p=0.5, respawn_p=0.2),
                 deadline=DEADLINE, policy="close_partial", record=True)
    assert res.trace.has_faults
    path = save_trace(str(tmp_path / "faulty"), res.trace)
    hdr = validate_trace_file(path)
    assert hdr["version"] == trace_mod.TRACE_FORMAT_VERSION == 2
    assert hdr["faults"] is True
    back = load_trace(path)
    assert back == res.trace
    rep = _sweep(TraceProcess(back), deadline=DEADLINE,
                 policy="close_partial")
    for nm in SCHEMES:
        assert np.array_equal(res.per_round[nm], rep.per_round[nm])


def test_trace_rejects_nan_but_accepts_inf():
    ones = np.ones((1, 1, 2, 2), np.float32)
    with pytest.raises(ValueError, match="NaN"):
        DelayTrace(np.where(ones > 0, np.nan, 1.0), ones)
    faulty = DelayTrace(np.where(ones > 0, np.inf, 1.0), ones)
    assert faulty.has_faults


# --------------------- (e) crash-aware adaptive scheduling -------------------

def _observe_only_worker_alive(sched, n, r, alive):
    obs = np.ones((n, r))
    arr = np.full((n, r), np.inf)
    arr[alive] = 0.5
    sched.observe(obs, arrivals=arr, t_done=1.0)


def test_scheduler_detects_dead_and_repairs_coverage():
    C = cyclic_to_matrix(N, R)
    s = AdaptiveScheduler(C, dead_after=2, target_k=2)
    assert not s.dead_workers().any()
    for _ in range(2):
        s.worker_of_row()
        _observe_only_worker_alive(s, N, R, alive=N - 1)
    dead = s.dead_workers()
    assert dead.sum() == N - 1 and not dead[N - 1]
    # the surviving worker's R rows still cover target_k=2 distinct tasks
    M = s.matrix()
    act = M[N - 1:][M[N - 1:] != MASKED]
    assert np.unique(act).size >= 2


def test_scheduler_raises_when_degradation_impossible():
    C = cyclic_to_matrix(N, R)
    s = AdaptiveScheduler(C, dead_after=2, target_k=K + 1)
    for _ in range(2):
        s.worker_of_row()
        _observe_only_worker_alive(s, N, R, alive=N - 1)
    with pytest.raises(ValueError,
                       match="graceful degradation impossible"):
        s.matrix()


def test_set_need_validates_and_prioritizes():
    C = cyclic_to_matrix(N, R)
    s = AdaptiveScheduler(C)
    with pytest.raises(ValueError, match="shape"):
        s.set_need(np.ones(N + 1, bool))
    s.set_need(None)                      # clearing is always legal
    s.set_need(np.zeros(N, bool))         # nothing needed == cleared
    assert s._need is None


# ----------------------- (f) fail-fast spec validation -----------------------

def test_engine_rejects_uncoverable_schedule():
    C = np.array([[0, MASKED], [0, MASKED], [1, MASKED],
                  [1, MASKED], [0, MASKED], [1, MASKED]])
    with pytest.raises(ValueError, match="covers only"):
        _sweep(IIDProcess(scenario1()),
               specs=[to_spec("bad", C, loads=(1,) * N)])


def test_roundspec_deadline_validation():
    with pytest.raises(ValueError, match="needs a deadline"):
        RoundConfig(n=N, r=R, k=K, kind="cs",
                    deadline_policy="close_partial")
    with pytest.raises(ValueError, match="deadline_policy"):
        RoundConfig(n=N, r=R, k=K, kind="cs", deadline=1.0,
                    deadline_policy="eventually")
    with pytest.raises(ValueError, match="deadline must be"):
        RoundConfig(n=N, r=R, k=K, kind="cs", deadline=0.0)
    spec = RoundConfig(n=N, r=R, k=K, kind="cs", deadline=1.0,
                       deadline_policy="reissue", adaptive=True)
    assert spec.deadline == 1.0


def test_engine_rejects_bad_policy_args():
    with pytest.raises(ValueError, match="unknown deadline policy"):
        _sweep(IIDProcess(scenario1()), deadline=DEADLINE, policy="later")
    with pytest.raises(ValueError, match="needs a"):
        _sweep(IIDProcess(scenario1()), policy="close_partial")
