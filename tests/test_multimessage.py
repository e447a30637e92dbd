"""Tests for the intra-round message axis (paper Sec. V-C) and the
censoring-aware adaptive feedback.

Covers the ISSUE-3 acceptance points:
  (a) the default message budget reproduces the pre-axis engine bit-exactly
      for every scheme kind (full multi-message for to/lb/tau/pcmm, one-shot
      for pc), and explicit ``messages=load`` equals the default;
  (b) every budget m matches an independent numpy oracle implementing the
      closing-slot grouping from raw draws (m=1 is the one-shot semantics
      the pc path has always used, applied to uncoded schemes);
  (c) ``sweep_rounds`` with m>1 is chunk-invariant;
  (d) the Sec. V-C ordering: more messages => no worse mean completion;
  (e) the closed-form multi-message coded expectations (eqs. 51-52 / 56-57
      generalized) match engine Monte-Carlo;
  (f) censored feedback: engine + AdaptiveScheduler observe only messages
      that beat the round deadline, monotonically in the deadline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MarkovRegimeProcess, ShiftedExponentialDelays,
                        adaptive_spec, completion_samples, cyclic_to_matrix,
                        ec2_cluster, heterogeneous_scales, lb_spec,
                        message_arrival_times, message_boundaries,
                        message_comm_delays, message_group_sizes,
                        message_slot_map, multimessage_coded_mean,
                        pc_spec, pc_threshold, pcmm_spec, pcmm_threshold,
                        scenario1, staircase_to_matrix, sweep, sweep_rounds,
                        task_arrival_samples, to_spec, trajectory_samples)


# ------------------------- message layout helpers ----------------------------

def test_message_layout_helpers():
    assert message_boundaries(5, 2).tolist() == [2, 4]
    assert message_group_sizes(5, 2).tolist() == [3, 2]
    assert message_slot_map(5, 2).tolist() == [2, 2, 2, 4, 4]
    assert message_slot_map(4, 1).tolist() == [3, 3, 3, 3]
    assert message_slot_map(4, 4).tolist() == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        message_boundaries(4, 0)
    with pytest.raises(ValueError):
        message_boundaries(4, 5)


def test_message_arrival_times_and_comm_delays():
    from repro.core import slot_arrival_times
    m = scenario1()
    T1, T2 = m.sample(jax.random.PRNGKey(0), 8, 5, 4)
    s = np.asarray(slot_arrival_times(T1, T2))     # eq. (1), same backend
    got = np.asarray(message_arrival_times(T1, T2, 2))
    smap = message_slot_map(4, 2)
    assert np.array_equal(got, s[..., smap])
    assert np.array_equal(np.asarray(message_arrival_times(T1, T2, 4)), s)
    d = np.asarray(message_comm_delays(T2, 2))
    assert np.array_equal(d, np.asarray(T2)[..., message_boundaries(4, 2)])
    assert np.array_equal(np.asarray(message_comm_delays(T2, 4)),
                          np.asarray(T2))


# ------------------- (a) default budget == pre-axis engine -------------------

def test_default_messages_bitmatch_explicit_full_budget():
    n, r, k, trials = 8, 4, 6, 1500
    m = scenario1()
    C = staircase_to_matrix(n, r)
    for default, explicit in (
            (to_spec("x", C), to_spec("x", C, messages=r)),
            (lb_spec(r), lb_spec(r, messages=r)),
            (pcmm_spec(r), pcmm_spec(r, messages=r))):
        a = np.asarray(completion_samples(default, m, n, trials=trials,
                                          seed=3, k=k))
        b = np.asarray(completion_samples(explicit, m, n, trials=trials,
                                          seed=3, k=k))
        assert (a == b).all(), default.kind
    tau_a = np.asarray(task_arrival_samples(C, m, trials=trials, seed=3))
    tau_b = np.asarray(task_arrival_samples(C, m, trials=trials, seed=3,
                                            messages=r))
    assert (tau_a == tau_b).all()


# ----------------- (b) every budget matches a numpy oracle -------------------

def _oracle_draws(model, n, r, trials, seed):
    """Per-trial draws under the engine's key convention: one key per
    trial, folded in from the base key by global trial id."""
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(seed), jnp.arange(trials, dtype=jnp.int32))
    T1s, T2s = [], []
    for i in range(trials):
        T1, T2 = model.sample(keys[i], 1, n, r)
        T1s.append(np.asarray(T1)[0])
        T2s.append(np.asarray(T2)[0])
    return np.stack(T1s), np.stack(T2s)


@pytest.mark.parametrize("messages", [1, 2, 3])
def test_engine_budgets_match_numpy_oracle(messages):
    n, r, k, trials = 7, 3, 5, 200
    model = ShiftedExponentialDelays()
    C = cyclic_to_matrix(n, r)
    T1, T2 = _oracle_draws(model, n, r, trials, seed=11)
    s = np.cumsum(T1, axis=-1) + T2
    s_msg = s[..., message_slot_map(r, messages)]
    # uncoded: min over copies, k-th order statistic
    tau = np.full((trials, n), np.inf)
    for w in range(n):
        for j in range(r):
            tau[:, C[w, j]] = np.minimum(tau[:, C[w, j]], s_msg[:, w, j])
    to_oracle = np.sort(tau, axis=-1)[:, k - 1]
    got = np.asarray(completion_samples(
        to_spec("x", C, messages=messages), model, n, trials=trials,
        seed=11, k=k))
    np.testing.assert_allclose(got, to_oracle, rtol=1e-6)
    # lb: k-th smallest over all remapped slot arrivals
    lb_oracle = np.sort(s_msg.reshape(trials, -1), axis=-1)[:, k - 1]
    got = np.asarray(completion_samples(
        lb_spec(r, messages=messages), model, n, trials=trials, seed=11,
        k=k))
    np.testing.assert_allclose(got, lb_oracle, rtol=1e-6)
    # pcmm: (2n-1)-th smallest over all remapped slot arrivals
    th = pcmm_threshold(n)
    pcmm_oracle = np.sort(s_msg.reshape(trials, -1), axis=-1)[:, th - 1]
    got = np.asarray(completion_samples(
        pcmm_spec(r, messages=messages), model, n, trials=trials, seed=11))
    np.testing.assert_allclose(got, pcmm_oracle, rtol=1e-6)


def test_m1_is_the_one_shot_semantics_for_every_kind():
    """m=1 applies the one-shot arrival the pc path has always used —
    cumulative compute through the last slot + its comm draw — to every
    scheme kind; pc itself stays bit-identical."""
    n, r, trials = 7, 3, 200
    model = ShiftedExponentialDelays()
    C = cyclic_to_matrix(n, r)
    T1, T2 = _oracle_draws(model, n, r, trials, seed=5)
    one_shot = np.cumsum(T1, axis=-1)[..., -1] + T2[..., -1]   # (trials, n)
    tau1 = np.asarray(task_arrival_samples(C, model, trials=trials, seed=5,
                                           messages=1))
    # every copy of task p arrives at its worker's one-shot time
    for p in range(n):
        holders = [w for w in range(n) if p in C[w]]
        np.testing.assert_allclose(tau1[:, p],
                                   one_shot[:, holders].min(axis=1),
                                   rtol=1e-6)
    # pc: unchanged by the axis (messages=1 is its only legal value)
    pc = np.asarray(completion_samples(pc_spec(r), model, n, trials=trials,
                                       seed=5))
    th = pc_threshold(n, r)
    np.testing.assert_allclose(
        pc[:, 0] if pc.ndim > 1 else pc,
        np.sort(one_shot, axis=-1)[:, th - 1], rtol=1e-6)


def test_messages_validation():
    n, r = 6, 3
    m = scenario1()
    C = cyclic_to_matrix(n, r)
    with pytest.raises(ValueError, match="messages"):
        sweep([to_spec("a", C, messages=0)], m, n, trials=8)
    with pytest.raises(ValueError, match="messages"):
        sweep([to_spec("a", C, messages=r + 1)], m, n, trials=8)
    with pytest.raises(ValueError, match="one-shot"):
        from repro.core import SchemeSpec
        sweep([SchemeSpec(name="p", kind="pc", r=r, messages=2)], m, n,
              trials=8)


# --------------------- (c) rounds axis chunk invariance ----------------------

def test_rounds_multimessage_chunk_invariant():
    n, r, k, trials, rounds = 6, 3, 5, 300, 4
    proc = MarkovRegimeProcess(base=scenario1(),
                               worker_scale=heterogeneous_scales(n, 2.0),
                               persistence=0.9)
    spec = to_spec("cs2", cyclic_to_matrix(n, r), messages=2)
    full = np.asarray(trajectory_samples(spec, proc, n, rounds=rounds, k=k,
                                         trials=trials, seed=0))
    part = np.asarray(trajectory_samples(spec, proc, n, rounds=rounds, k=k,
                                         trials=trials, seed=0, chunk=77))
    assert full.shape == (trials, rounds)
    assert (full == part).all()
    res = sweep_rounds([spec], proc, n, rounds=rounds, k=k, trials=trials,
                       seed=0, chunk=128)
    np.testing.assert_allclose(res.per_round["cs2"], full.mean(0), rtol=1e-5)


# ------------------------ (d) Sec. V-C mean ordering -------------------------

def test_more_messages_never_hurt_on_average():
    """Paired (common-random-number) means: completion time is
    non-increasing in the message budget for CS, SS, LB and PCMM."""
    n, r, k, trials = 10, 4, 8, 4000
    from repro.core import ec2_like
    model = ec2_like(n, seed=0)
    specs = []
    for m in (1, 2, r):
        specs += [to_spec(f"cs{m}", cyclic_to_matrix(n, r), messages=m),
                  to_spec(f"ss{m}", staircase_to_matrix(n, r), messages=m),
                  lb_spec(r, name=f"lb{m}", messages=m),
                  pcmm_spec(r, name=f"pcmm{m}", messages=m)]
    res = sweep(specs, model, n, trials=trials, seed=0, ks=k)
    for fam in ("cs", "ss", "lb", "pcmm"):
        t = [res.at_k(f"{fam}{m}", k) for m in (1, 2, r)]
        assert t[2] <= t[1] <= t[0], (fam, t)


# ------------- (e) closed-form coded expectations vs engine MC ---------------

def _sexp_pdf(shift, mean):
    return lambda t: np.where(
        t >= shift, np.exp(-np.minimum((t - shift) / mean, 700.0)) / mean,
        0.0)


def test_multimessage_closed_form_matches_mc():
    n, r = 8, 4
    model = ShiftedExponentialDelays()
    pdf1 = _sexp_pdf(1e-4, 5e-5)
    pdf2 = _sexp_pdf(2e-4, 1e-4)
    specs = [pcmm_spec(r, name=f"pcmm{m}", messages=m)
             for m in (1, 2, r)] + [pc_spec(r)]
    res = sweep(specs, model, n, trials=30000, seed=0)
    for m in (1, 2, r):
        cf = multimessage_coded_mean(n, r, m, pdf1, pdf2, tmax=8e-3,
                                     npts=4096)
        assert np.isclose(cf, res.at_k(f"pcmm{m}"), rtol=0.03), m
    # eqs. 51-52 exactly: PC is the m=1 case at the full-worker threshold
    th = (pc_threshold(n, r) - 1) * r + 1
    cf = multimessage_coded_mean(n, r, 1, pdf1, pdf2, tmax=8e-3, npts=4096,
                                 threshold=th)
    assert np.isclose(cf, res.at_k("pc"), rtol=0.03)


# -------------------------- (f) censored feedback ----------------------------

def test_censored_adaptive_still_beats_static():
    """Restricting feedback to messages that beat the round deadline keeps
    the adaptive edge on persistent heterogeneous clusters (delivered
    messages still identify the fast workers; silent workers are ranked
    slowest by construction)."""
    n, r, k = 10, 3, 8
    proc = ec2_cluster(n, spread=3.0, p_slow=0.25, persistence=0.95,
                       slow=8.0)
    cs = cyclic_to_matrix(n, r)
    specs = [to_spec("cs", cs), to_spec("ss", staircase_to_matrix(n, r)),
             adaptive_spec("adapt", cs)]
    res_c = sweep_rounds(specs, proc, n, rounds=16, k=k, trials=800, seed=0,
                         censored_feedback=True)
    adapt = res_c.mean_round("adapt")
    assert adapt < res_c.mean_round("cs")
    assert adapt < res_c.mean_round("ss")
    # censoring changes the feedback stream, so the trajectories differ
    # from the idealized full-feedback run (statics are untouched)
    res_u = sweep_rounds(specs, proc, n, rounds=16, k=k, trials=800, seed=0)
    assert np.array_equal(res_u.per_round["cs"], res_c.per_round["cs"])
    assert not np.array_equal(res_u.per_round["adapt"],
                              res_c.per_round["adapt"])


def test_censored_feedback_requires_adaptive_aggregator():
    from repro.core import RoundConfig, StragglerAggregator
    with pytest.raises(ValueError, match="adaptive"):
        StragglerAggregator(RoundConfig(n=6, r=3, k=4, kind="ss",
                                        censored_feedback=True), scenario1())


def test_censored_rounds_chunk_invariant():
    n, r, k = 6, 3, 5
    proc = MarkovRegimeProcess(base=scenario1(),
                               worker_scale=heterogeneous_scales(n, 2.0),
                               persistence=0.9)
    spec = adaptive_spec("a", cyclic_to_matrix(n, r), messages=2)
    full = np.asarray(trajectory_samples(spec, proc, n, rounds=5, k=k,
                                         trials=300, seed=0,
                                         censored_feedback=True))
    part = np.asarray(trajectory_samples(spec, proc, n, rounds=5, k=k,
                                         trials=300, seed=0, chunk=77,
                                         censored_feedback=True))
    assert (full == part).all()
