"""Trace-driven delay sources (core/trace.py + the record/replay paths of
the engine, aggregator, and launcher).

Covers the ISSUE-5 acceptance points:
  (a) the DelayTrace container + versioned on-disk format: validation,
      save/load round-trip, digest/version checks;
  (b) TraceProcess replay semantics: padding/truncation policies per axis,
      trial cycling, determinism (keys ignored);
  (c) round-trip bit-exactness — a trace recorded from ``sweep_rounds``
      under any parametric process, replayed via ``TraceProcess``,
      reproduces the recording run's per-round completion times and
      adaptive decisions exactly, across scheme kinds, message budgets,
      and ragged loads (property test), under any trial chunking;
  (d) calibration: ``calibrate_trace`` recovers a known generating
      cluster's regime parameters and worker scales;
  (e) the round API (aggregator) accepts trace-backed processes;
  (f) the ``as_process`` coercion + clear TypeError satellite.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (AR1Process, CalibrationReport, DelayTrace,
                        IIDProcess, MarkovRegimeProcess, RoundConfig,
                        StragglerAggregator, TraceProcess, adaptive_spec,
                        as_process, calibrate_trace, cyclic_to_matrix,
                        ec2_cluster, lb_spec, load_trace, save_trace,
                        scenario1, staircase_to_matrix, sweep_rounds,
                        to_spec, trajectory_samples, validate_trace_file)
import repro.core.trace as trace_mod

N, R, K, ROUNDS, TRIALS = 6, 3, 4, 5, 48

PROCESSES = {
    "iid": IIDProcess(scenario1()),
    "markov": ec2_cluster(N, spread=3.0, persistence=0.9, seed=1),
    "ar1": AR1Process(base=scenario1(), rho=0.8, sigma=0.4),
}
LOADS = (3, 1, 2, 3, 2, 1)
SPEC_SETS = {
    "dense": [to_spec("cs", cyclic_to_matrix(N, R)), lb_spec(R)],
    "ragged": [to_spec("cs", cyclic_to_matrix(N, R), loads=LOADS),
               lb_spec(R, loads=LOADS)],
    "budget": [to_spec("ss", staircase_to_matrix(N, R), messages=2),
               to_spec("ss1", staircase_to_matrix(N, R), messages=1)],
    "budget-ragged": [to_spec("mix", cyclic_to_matrix(N, R), messages=2,
                              loads=LOADS)],
    "adaptive": [adaptive_spec("ad", cyclic_to_matrix(N, R)),
                 adaptive_spec("rb", cyclic_to_matrix(N, R + 1),
                               loads=(R,) * N, rebalance=True)],
}


def _small_trace(rounds=3, trials=2, n=4, r=2, seed=0):
    rng = np.random.default_rng(seed)
    T1 = rng.uniform(0.5, 1.5, (rounds, trials, n, r)).astype(np.float32)
    T2 = rng.uniform(0.5, 1.5, (rounds, trials, n, r)).astype(np.float32)
    return DelayTrace(T1, T2, meta={"source": "test"})


# --------------------- (a) container + on-disk format ------------------------

def test_trace_container_validation():
    tr = _small_trace()
    assert (tr.rounds, tr.trials, tr.n, tr.r) == (3, 2, 4, 2)
    # 3-D input gets a singleton trial axis (a single recorded realization)
    one = DelayTrace(tr.T1[:, 0], tr.T2[:, 0])
    assert one.trials == 1 and one.rounds == 3
    with pytest.raises(ValueError, match="shape"):
        DelayTrace(np.ones((3, 2)), np.ones((3, 2)))
    with pytest.raises(ValueError, match="mismatch"):
        DelayTrace(tr.T1, tr.T2[:, :, :2])
    with pytest.raises(ValueError, match="NaN"):
        DelayTrace(np.full((1, 1, 2, 2), np.nan), np.ones((1, 1, 2, 2)))
    # +inf cells are legal since format v2: a fault-censored result that
    # never arrived.  -inf / non-positive delays stay rejected.
    faulty = DelayTrace(np.full((1, 1, 2, 2), np.inf),
                        np.ones((1, 1, 2, 2)))
    assert faulty.has_faults and not tr.has_faults
    with pytest.raises(ValueError, match="positive"):
        DelayTrace(np.full((1, 1, 2, 2), -np.inf), np.ones((1, 1, 2, 2)))
    with pytest.raises(ValueError, match="positive"):
        DelayTrace(np.zeros((1, 1, 2, 2)), np.ones((1, 1, 2, 2)))
    with pytest.raises(AttributeError):
        tr.T1 = None
    # content identity: equal tables hash/compare equal, meta is advisory
    same = DelayTrace(tr.T1.copy(), tr.T2.copy(), meta={"other": 1})
    assert same == tr and hash(same) == hash(tr)
    assert _small_trace(seed=1) != tr
    # the container owns copies: the caller's float32 arrays stay writable
    # and later caller mutations don't leak into the frozen trace
    mine = np.full((1, 1, 2, 2), 2.0, np.float32)
    held = DelayTrace(mine, mine)
    mine[0, 0, 0, 0] = 9.0
    assert held.T1[0, 0, 0, 0] == 2.0


def test_save_load_roundtrip(tmp_path):
    tr = _small_trace()
    path = save_trace(str(tmp_path / "t"), tr)
    assert path.endswith(".npz")
    back = load_trace(path)
    assert back == tr
    assert back.meta["source"] == "test"
    hdr = validate_trace_file(path)
    # fault-free traces keep writing version 1 so pre-fault readers still
    # load them; only +inf cells bump the header to the current version
    assert hdr["version"] == 1 <= trace_mod.TRACE_FORMAT_VERSION
    assert hdr["rounds"] == 3 and hdr["n"] == 4


def test_load_rejects_corruption_and_new_versions(tmp_path):
    tr = _small_trace()
    path = save_trace(str(tmp_path / "t"), tr)
    # tamper with a table: digest check fires
    with np.load(path) as z:
        parts = dict(z)
    parts["T1"] = parts["T1"] + 0.25
    bad = str(tmp_path / "bad.npz")
    np.savez(bad, **parts)
    with pytest.raises(ValueError, match="digest"):
        load_trace(bad)
    # a future format version is rejected, not misread
    import json
    hdr = json.loads(bytes(parts["header"].tobytes()).decode())
    hdr["version"] = trace_mod.TRACE_FORMAT_VERSION + 1
    parts["T1"] = tr.T1
    parts["header"] = np.frombuffer(json.dumps(hdr).encode(), np.uint8)
    newer = str(tmp_path / "newer.npz")
    np.savez(newer, **parts)
    with pytest.raises(ValueError, match="newer"):
        load_trace(newer)
    # not a trace file at all
    np.savez(str(tmp_path / "x.npz"), T1=tr.T1)
    with pytest.raises(ValueError, match="header"):
        load_trace(str(tmp_path / "x.npz"))


# ------------------------- (b) replay semantics ------------------------------

def _step_tables(proc, n, r, trials=4, steps=1):
    keys = jax.random.split(jax.random.PRNGKey(0), trials)
    state = proc.init(keys, n)
    for _ in range(steps):
        state, T1, T2 = proc.step(state, keys, n, r)
    return np.asarray(T1), np.asarray(T2)


def test_replay_reads_tables_and_ignores_keys():
    tr = _small_trace(rounds=3, trials=4, n=4, r=2)
    proc = TraceProcess(tr)
    T1, _ = _step_tables(proc, 4, 2, trials=4)
    assert np.array_equal(T1, tr.T1[0])
    # second step reads round 1
    T1b, _ = _step_tables(proc, 4, 2, trials=4, steps=2)
    assert np.array_equal(T1b, tr.T1[1])
    # truncation: smaller n / r read the leading block
    T1c, _ = _step_tables(proc, 3, 1, trials=4)
    assert np.array_equal(T1c, tr.T1[0, :, :3, :1])
    # trial cycling: more trials than recorded wrap around
    T1d, _ = _step_tables(proc, 4, 2, trials=6)
    assert np.array_equal(T1d[4:], tr.T1[0, :2])


def test_replay_padding_policies():
    tr = _small_trace(rounds=2, trials=1, n=3, r=2)
    strict = TraceProcess(tr)
    with pytest.raises(ValueError, match="pad_workers='cycle'"):
        strict.init(jax.random.split(jax.random.PRNGKey(0), 2), 5)
    with pytest.raises(ValueError, match="pad_slots='cycle'"):
        _step_tables(strict, 3, 4)
    with pytest.raises(ValueError, match="pad_rounds='cycle'"):
        strict.check_rounds(3)
    strict.check_rounds(2)

    T1w, _ = _step_tables(TraceProcess(tr, pad_workers="cycle"), 5, 2,
                          trials=1)
    assert np.array_equal(T1w[:, 3:], tr.T1[0, :, :2])
    T1s, _ = _step_tables(TraceProcess(tr, pad_slots="cycle"), 3, 4,
                          trials=1)
    assert np.array_equal(T1s[..., 2:], tr.T1[0, ..., :2])
    cyc = TraceProcess(tr, pad_rounds="cycle")
    T1c, _ = _step_tables(cyc, 3, 2, trials=1, steps=3)  # round 2 -> table 0
    assert np.array_equal(T1c, tr.T1[0])
    hold = TraceProcess(tr, pad_rounds="hold")
    T1h, _ = _step_tables(hold, 3, 2, trials=1, steps=4)  # held at final
    assert np.array_equal(T1h, tr.T1[1])
    # sample_rounds honors the policy hooks
    T1all, _ = hold.sample_rounds(jax.random.PRNGKey(0), 1, 3, 2, 4)
    assert np.array_equal(np.asarray(T1all[-1]), tr.T1[1])
    with pytest.raises(ValueError, match="recorded only"):
        strict.sample_rounds(jax.random.PRNGKey(0), 1, 3, 2, 4)
    with pytest.raises(ValueError, match="pad_rounds"):
        TraceProcess(tr, pad_rounds="wrap")
    with pytest.raises(TypeError, match="DelayTrace"):
        TraceProcess(np.ones((2, 1, 3, 2)))


def test_start_round_offsets_replay():
    """Resuming a checkpointed run mid-trace: replay starts at the round
    the next step originally consumed, and the horizon check covers the
    offset."""
    tr = _small_trace(rounds=3, trials=1, n=3, r=2)
    off = TraceProcess(tr, start_round=1)
    T1, _ = _step_tables(off, 3, 2, trials=1)
    assert np.array_equal(T1, tr.T1[1])
    off.check_rounds(2)
    with pytest.raises(ValueError, match="start_round=1"):
        off.check_rounds(3)
    with pytest.raises(ValueError, match="start_round"):
        TraceProcess(tr, start_round=-1)


# ---------------------- (c) round-trip bit-exactness -------------------------

@settings(deadline=None, max_examples=10)
@given(st.sampled_from(sorted(PROCESSES)), st.sampled_from(sorted(SPEC_SETS)),
       st.integers(1, TRIALS))
def test_replay_bit_exact_property(proc_name, set_name, chunk):
    """The acceptance criterion: any (process, scheme-kind, message
    budget, ragged loads) recording replays bit-exactly — identical
    per-trial completion times (hence identical adaptive decisions) under
    any replay chunking, and identical per-round means at the recording's
    chunking."""
    process, specs = PROCESSES[proc_name], SPEC_SETS[set_name]
    censored = set_name == "adaptive"
    res = sweep_rounds(specs, process, N, rounds=ROUNDS, k=K, trials=TRIALS,
                       seed=0, chunk=16, censored_feedback=censored,
                       record_trace=True)
    assert res.trace.T1.shape == (ROUNDS, TRIALS, N,
                                  max(sp.load for sp in specs))
    rep = sweep_rounds(specs, TraceProcess(res.trace), N, rounds=ROUNDS,
                       k=K, trials=TRIALS, seed=77, chunk=16,
                       censored_feedback=censored)
    for sp in specs:
        assert np.array_equal(res.per_round[sp.name], rep.per_round[sp.name])
        assert np.array_equal(res.wallclock[sp.name], rep.wallclock[sp.name])
    # per-trial trajectories are chunking-invariant bit-exact
    sp = specs[0]
    samp, tr = trajectory_samples(sp, process, N, rounds=ROUNDS, k=K,
                                  trials=TRIALS, seed=0, chunk=16,
                                  censored_feedback=censored,
                                  record_trace=True)
    rep_s = trajectory_samples(sp, TraceProcess(tr), N, rounds=ROUNDS, k=K,
                               trials=TRIALS, seed=3, chunk=chunk,
                               censored_feedback=censored)
    assert np.array_equal(np.asarray(samp), np.asarray(rep_s))


def test_trace_field_default_none():
    res = sweep_rounds(SPEC_SETS["dense"], PROCESSES["iid"], N,
                       rounds=2, k=K, trials=8, seed=0)
    assert res.trace is None
    samp = trajectory_samples(SPEC_SETS["dense"][0], PROCESSES["iid"], N,
                              rounds=2, k=K, trials=8, seed=0)
    assert np.asarray(samp).shape == (8, 2)


# ----------------------------- (d) calibration -------------------------------

def test_calibration_recovers_generating_cluster():
    scale = (0.6, 1.0, 1.8, 0.9)
    truth = MarkovRegimeProcess(base=scenario1(), worker_scale=scale,
                                p_slow=0.3, persistence=0.85, slow=6.0)
    res = sweep_rounds([to_spec("cs", cyclic_to_matrix(4, 2))], truth, 4,
                       rounds=60, k=3, trials=32, seed=3, record_trace=True)
    rep = calibrate_trace(res.trace)
    assert isinstance(rep, CalibrationReport)
    assert abs(rep.p_slow - 0.3) < 0.08
    assert abs(rep.persistence - 0.85) < 0.08
    assert abs(rep.slow - 6.0) / 6.0 < 0.25
    # worker ordering survives (scales are normalized to geo-mean 1)
    assert (np.argsort(rep.worker_scale)
            == np.argsort(np.asarray(scale))).all()
    # fit-quality report: moments of the fitted process track the trace
    assert rep.mean_rel_err < 0.15
    assert rep.comm_mean_rel_err < 0.15
    assert rep.lag1_trace > 0.4 and rep.lag1_fit > 0.4
    assert "p_slow" in rep.summary()


def test_calibration_homogeneous_degenerates_gracefully():
    res = sweep_rounds([to_spec("cs", cyclic_to_matrix(4, 2))],
                       IIDProcess(scenario1()), 4, rounds=20, k=3,
                       trials=32, seed=0, record_trace=True)
    rep = calibrate_trace(res.trace)
    assert rep.p_slow == 0.0 and rep.slow == 1.0 and rep.persistence == 0.0
    assert max(rep.worker_scale) / min(rep.worker_scale) < 1.2
    assert rep.mean_rel_err < 0.1


# --------------------- (e) round API on trace processes ----------------------

def test_aggregator_replays_trace_deterministically():
    tr = _small_trace(rounds=4, trials=1, n=4, r=2, seed=5)
    spec = RoundConfig(n=4, r=2, k=3, kind="ss")

    def run():
        agg = StragglerAggregator(spec, tr)        # DelayTrace coerced
        out = []
        for i in range(4):
            _, t_done = agg.round_mask(jax.random.PRNGKey(i))
            out.append(float(t_done))
        return out, agg

    a, agg = run()
    b, _ = run()
    assert a == b                    # keys are ignored: pure replay
    # horizon: a 5th round exceeds the strict trace
    with pytest.raises(ValueError, match="recorded only"):
        agg.round_mask(jax.random.PRNGKey(99))
    # expected_completion caps its default rounds at the trace horizon
    assert np.isfinite(agg.expected_completion(trials=16))


# ------------------------- (f) as_process coercion ---------------------------

def test_as_process_accepts_traces_and_names_protocol():
    tr = _small_trace()
    p = as_process(tr)
    assert isinstance(p, TraceProcess) and p.trace is tr
    tp = TraceProcess(tr, pad_rounds="cycle")
    assert as_process(tp) is tp
    with pytest.raises(TypeError) as ei:
        as_process({"not": "a delay source"})
    msg = str(ei.value)
    # the satellite: the error names the accepted types and the protocol
    for needle in ("DelayProcess", "init/step", "DelayModel", "DelayTrace",
                   "dict"):
        assert needle in msg, (needle, msg)
