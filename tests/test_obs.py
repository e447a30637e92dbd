"""The program's spans and counters (``repro.obs``): nothing is recorded
without a profiler session; under one, spans reach both the profiler's
trace and the in-memory records; the planner's and the engine's results
do not depend on tracing."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (GridSpec, cyclic_to_matrix, lb_spec, plan,
                        scenario1, sweep, to_spec)

MODEL = scenario1()
N = 8
GS = GridSpec(n=N, families=("cs", "ss", "lb", "pc"), loads=(2, 4),
              messages=(None, 2), trials=1024, seed=3)
SPECS = [to_spec("cs", cyclic_to_matrix(N, 4)), lb_spec(4, name="lb")]


def _plan():
    return plan(GS, MODEL, k=N, base_trials=256, eta=2)


def _sweep():
    return sweep(SPECS, MODEL, N, trials=600, chunk=256, seed=5)


def _host_events(trace_dir: Path) -> set:
    from jax.profiler import ProfileData
    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler session: a nest of spans and counts, one plan() and
    one sweep()."""
    untraced = (_plan(), _sweep())          # also compiles outside the trace
    obs.reset()
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        with obs.span("test.outer", case=1):
            obs.count("test.items", 2)
            with obs.span("test.inner"):
                obs.count("test.items", 3)
        with obs.span("test.second"):
            pass
        records = obs.spans()
        plan_res = _plan()
        plan_records = obs.spans()[len(records):]
        sweep_res = _sweep()
    out = {"records": records, "plan": plan_res, "plan_records": plan_records,
           "sweep": sweep_res, "all": obs.spans(), "counters": obs.counters(),
           "untraced": untraced, "events": _host_events(d)}
    obs.reset()
    return out


def test_nothing_recorded_without_a_profiler_session():
    obs.reset()
    with obs.span("test.off"):
        obs.count("test.off", 1)
    _sweep()
    assert obs.spans() == [] and obs.counters() == {}


def test_span_reaches_the_trace_and_the_records(traced):
    assert {"test.outer", "test.inner", "test.second"} <= traced["events"]
    by = {s.name: s for s in traced["records"]}
    outer, inner, second = by["test.outer"], by["test.inner"], by["test.second"]
    assert outer.parent is None and outer.request == outer.id
    assert inner.parent == outer.id and inner.request == outer.id
    assert second.parent is None and second.request == second.id != outer.id
    assert outer.attrs == {"case": 1}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_counter_adds(traced):
    assert traced["counters"]["test.items"] == 5


def test_self_time_is_duration_less_children_cover():
    def sp(i, parent, a, b):
        return obs.Span(i, f"s{i}", parent, 1, a, b, {})
    # children overlap each other and one reaches past the parent's end
    recs = [sp(1, None, 0, 100), sp(2, 1, 10, 30), sp(3, 1, 20, 50),
            sp(4, 1, 90, 120), sp(5, 2, 12, 14)]
    own = obs.self_ns(recs)
    assert own == {1: 100 - 40 - 10, 2: 18, 3: 30, 4: 30, 5: 2}
    assert obs.summary(recs)["s1"] == (1, 100, 50)


def test_span_cap_counts_what_it_drops(monkeypatch):
    obs.reset()
    monkeypatch.setattr(obs, "MAX_SPANS", 2)
    monkeypatch.setattr(obs, "_is_enabled", lambda: True)
    for _ in range(5):
        with obs.span("test.capped"):
            pass
    assert len(obs.spans()) == 2 and obs.counters() == {"obs.dropped": 3}
    obs.reset()


def test_plan_same_with_tracing_on_and_off(traced):
    on, off = traced["plan"], traced["untraced"][0]
    assert on.winner == off.winner
    assert on.predicted_mean == off.predicted_mean
    assert on.predicted_stderr == off.predicted_stderr
    assert on.trials_spent == off.trials_spent
    assert on.lb_mean == off.lb_mean
    assert on.points == off.points


def test_sweep_same_with_tracing_on_and_off(traced):
    on, off = traced["sweep"], traced["untraced"][1]
    for name in on.means:
        np.testing.assert_array_equal(on.means[name], off.means[name])
        np.testing.assert_array_equal(on.stderr[name], off.stderr[name])


def test_plan_records_its_phases(traced):
    recs = traced["plan_records"]
    rungs = len(traced["plan"].trajectory)
    assert rungs >= 2
    names = [s.name for s in recs]
    roots = [s for s in recs if s.parent is None]
    assert [s.name for s in roots] == ["plan.decide"]
    assert all(s.request == roots[0].id for s in recs)
    assert names.count("plan.rung") == rungs
    assert names.count("plan.race") == rungs
    assert names.count("engine.extend") == rungs
    for one in ("plan.prune", "plan.select", "plan.lb_sweep"):
        assert names.count(one) == 1
    by_id = {s.id: s for s in recs}
    for s in recs:
        if s.name in ("plan.race", "engine.extend"):
            assert by_id[s.parent].name == "plan.rung"
    assert "engine.extend" in traced["events"]


def test_sweep_records_dispatch_wait_combine(traced):
    sweep_recs = traced["all"][len(traced["records"])
                               + len(traced["plan_records"]):]
    (root,) = [s for s in sweep_recs if s.parent is None]
    assert root.name == "engine.sweep"
    kids = sorted((s for s in sweep_recs if s.parent == root.id),
                  key=lambda s: s.start_ns)
    assert [s.name for s in kids] == ["engine.dispatch", "engine.wait",
                                      "engine.combine"]
    assert traced["counters"]["engine.fetched_bytes"] > 0


def test_sweep_counts_rank_count_rows(tmp_path, sweep11_specs):
    """``engine.select_rows``: every dispatched (trial, scheme) row whose
    order statistic is a rank count, padding included: seven of the
    sweep11 mix's eleven columns (the five TO schemes and both pc; lb and
    pcmm rank 256-wide windows).  Nothing is counted untraced."""
    def run():
        return sweep(sweep11_specs, MODEL, 16, trials=600, chunk=256, ks=16,
                     seed=2)

    obs.reset()
    run()                                    # also compiles outside the trace
    assert obs.counters() == {}
    with jax.profiler.trace(str(tmp_path)):
        run()
        counted = obs.counters()["engine.select_rows"]
    obs.reset()
    assert counted == 3 * 256 * 7            # 600 trials pad to 3 chunks


def test_plan_cli_profile_prints_the_spans(tmp_path, capsys):
    from repro.launch import plan as plan_cli
    rc = plan_cli.main([
        "--n", str(N), "--families", "cs", "ss", "lb", "pc",
        "--loads", "2", "4", "--messages", "none", "2", "--trials", "1024",
        "--seed", "3", "--base-trials", "256", "--eta", "2", "--k", str(N),
        "--out", str(tmp_path / "plan.json"),
        "--profile", str(tmp_path / "trace")])
    assert rc == 0
    rows = {ln.split()[0]: ln.split()[1:] for ln in
            capsys.readouterr().out.splitlines() if ln.startswith("plan.")}
    assert rows["plan.decide"][0] == "1" and rows["plan.rung"][0] == "3"
    assert list(tmp_path.glob("trace/plugins/profile/*/*.xplane.pb"))
    obs.reset()


def _rounds():
    from repro.core import adaptive_spec, ec2_cluster, sweep_rounds
    specs = [to_spec("cs", cyclic_to_matrix(N, 2)),
             adaptive_spec("adapt", cyclic_to_matrix(N, 2)),
             adaptive_spec("rebal", cyclic_to_matrix(N, 4), loads=(2,) * N,
                           rebalance=True)]
    return sweep_rounds(specs, ec2_cluster(N, persistence=0.9), N, rounds=3,
                        k=6, trials=600, chunk=256, seed=4,
                        censored_feedback=True)


@pytest.fixture(scope="module")
def traced_rounds(tmp_path_factory):
    """One ``sweep_rounds`` under a profiler session, and one without."""
    untraced = _rounds()                    # also compiles outside the trace
    obs.reset()
    with jax.profiler.trace(str(tmp_path_factory.mktemp("rounds"))):
        res = _rounds()
        out = {"res": res, "untraced": untraced, "spans": obs.spans(),
               "counters": obs.counters()}
    obs.reset()
    return out


def test_rounds_records_dispatch_wait_combine(traced_rounds):
    recs = traced_rounds["spans"]
    (root,) = [s for s in recs if s.parent is None]
    assert root.name == "engine.rounds"
    kids = sorted((s for s in recs if s.parent == root.id),
                  key=lambda s: s.start_ns)
    assert [s.name for s in kids] == ["engine.dispatch", "engine.wait",
                                      "engine.combine"]
    assert all(s.request == root.id for s in recs)


def test_rounds_counts_greedy_and_rebalance_rows(traced_rounds):
    # 600 trials pad to 3 chunks of 256; 3 rounds; two adaptive schemes,
    # one of them re-balancing
    got = traced_rounds["counters"]
    assert got["engine.greedy_rows"] == 3 * 256 * 3 * 2
    assert got["engine.rebalance_rows"] == 3 * 256 * 3 * 1


def test_rounds_counts_no_tie_rows_on_continuous_estimates(traced_rounds):
    # EMAs of continuous draws never tie at the re-balance's threshold
    assert traced_rounds["counters"]["engine.rebalance_tie_rows"] == 0


def test_rounds_counts_tie_rows_where_estimates_tie(tmp_path):
    """Delays equal across workers give equal estimates every round; on
    an uneven budget (17 slots over 8 workers) the threshold then falls
    inside a level of equal slot costs, and every chunk-round takes the
    descent.  Tracing on and off give identical results."""
    from repro.core import DelayTrace, TraceProcess, adaptive_spec, sweep_rounds
    rounds = 3
    ones = np.ones((rounds, N, 4), np.float32)
    process = TraceProcess(DelayTrace(ones, ones))

    def run():
        return sweep_rounds(
            [adaptive_spec("rebal", cyclic_to_matrix(N, 4),
                           loads=(3,) + (2,) * (N - 1), rebalance=True)],
            process, N, rounds=rounds, k=6, trials=600, chunk=256, seed=4)

    obs.reset()
    off = run()
    with jax.profiler.trace(str(tmp_path)):
        on = run()
        got = obs.counters()
    obs.reset()
    assert got["engine.rebalance_tie_rows"] == 3 * 256 * rounds
    assert got["engine.rebalance_rows"] == 3 * 256 * rounds
    for field in ("per_round", "stderr", "wallclock", "wallclock_stderr"):
        np.testing.assert_array_equal(getattr(on, field)["rebal"],
                                      getattr(off, field)["rebal"])


def test_rounds_same_with_tracing_on_and_off(traced_rounds):
    on, off = traced_rounds["res"], traced_rounds["untraced"]
    for field in ("per_round", "stderr", "wallclock", "wallclock_stderr"):
        for name, v in getattr(off, field).items():
            np.testing.assert_array_equal(getattr(on, field)[name], v)
