"""Racing planner (repro.core.planner) and its substrate: resumable
sweep extension bit-exactness, CRN paired-difference variance reduction,
rebalance x messages engine support, ``GridResult.best_cell``, planner
agreement with the exhaustive grid, and the ``repro.launch.plan`` CLI.

The multi-device legs need >= 4 devices; CI forces them on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import json

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (GridSpec, MarkovRegimeProcess, PlanResult,
                        RoundConfig, StragglerAggregator, adaptive_spec,
                        cyclic_to_matrix, delay_model_pdfs, lb_spec,
                        operating_point_mean_lb, plan, resumable_sweep,
                        scenario1, stream_grid, sweep, sweep_rounds, to_spec,
                        trajectory_samples, truncated_gaussian_pdf)
from repro.core import montecarlo as mc
from repro.core import planner as planner_mod
from repro.launch import grid as grid_cli
from repro.launch import plan as plan_cli

multidev = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")

MODEL = scenario1()
N = 8


def _specs(ragged: bool):
    C = cyclic_to_matrix(N, 4)
    if ragged:
        loads = np.array([4, 3, 2, 1, 4, 3, 2, 1])
        return [to_spec("a", C, loads=loads), lb_spec(4, name="b")]
    return [to_spec("a", C), lb_spec(4, name="b")]


def _assert_same_result(res_a, res_b):
    for nm in res_a.means:
        np.testing.assert_array_equal(res_a.means[nm], res_b.means[nm])
        np.testing.assert_array_equal(res_a.stderr[nm], res_b.stderr[nm])
    assert res_a.trials == res_b.trials


# ---------------------------------------------------------------------------
# resumable extension: bit-exact vs a fresh sweep at the combined count
# ---------------------------------------------------------------------------

class TestResumableSweep:
    @pytest.mark.parametrize("ragged", [False, True])
    @pytest.mark.parametrize("ks", [None, 5])
    def test_extension_matches_fresh_sweep_bitwise(self, ragged, ks):
        rs = resumable_sweep(_specs(ragged), MODEL, N, seed=3, chunk=64,
                             ks=ks, keep_samples=True)
        for total in (128, 256, 1024):
            rs.extend_trials(total)
            fresh = sweep(_specs(ragged), MODEL, N, trials=total, seed=3,
                          chunk=64, ks=ks)
            _assert_same_result(rs.result(), fresh)

    def test_samples_match_completion_samples(self):
        rs = resumable_sweep(_specs(False), MODEL, N, seed=0, chunk=32,
                             ks=5, keep_samples=True)
        rs.extend_trials(96)
        got = rs.samples()
        for sp in _specs(False):
            ref = mc.completion_samples(sp, MODEL, N, trials=96, seed=0,
                                        chunk=32, k=5)
            np.testing.assert_array_equal(
                np.asarray(got[sp.name]).ravel(), np.asarray(ref).ravel())

    def test_non_aligned_extension_is_terminal(self):
        rs = resumable_sweep(_specs(False), MODEL, N, seed=0, chunk=64)
        rs.extend_trials(100)          # not a multiple of 64: terminal
        fresh = sweep(_specs(False), MODEL, N, trials=100, seed=0, chunk=64)
        _assert_same_result(rs.result(), fresh)
        with pytest.raises(ValueError, match="chunk"):
            rs.extend_trials(200)

    def test_extend_must_grow(self):
        rs = resumable_sweep(_specs(False), MODEL, N, seed=0, chunk=64)
        rs.extend_trials(64)
        with pytest.raises(ValueError):
            rs.extend_trials(64)

    def test_narrow_keeps_survivor_bitwise(self):
        rs = resumable_sweep(_specs(False), MODEL, N, seed=7, chunk=64,
                             keep_samples=True)
        rs.extend_trials(128)
        rs.narrow(["a"])
        rs.extend_trials(512)
        # the survivor must equal a fresh *two-spec* run (the original
        # r_max shape is what keeps CRN pairing intact after narrowing)
        fresh = sweep(_specs(False), MODEL, N, trials=512, seed=7, chunk=64)
        got = rs.result()
        np.testing.assert_array_equal(got.means["a"], fresh.means["a"])
        np.testing.assert_array_equal(got.stderr["a"], fresh.stderr["a"])
        assert "b" not in got.means
        with pytest.raises(ValueError):
            rs.narrow(["nope"])

    @multidev
    @pytest.mark.parametrize("ragged", [False, True])
    def test_extension_bitwise_across_device_counts(self, ragged):
        res = {}
        for d in (1, 4):
            rs = resumable_sweep(_specs(ragged), MODEL, N, seed=5, chunk=64,
                                 devices=jax.devices()[:d])
            rs.extend_trials(256)
            rs.extend_trials(1024)
            res[d] = rs.result()
        _assert_same_result(res[1], res[4])
        fresh = sweep(_specs(ragged), MODEL, N, trials=1024, seed=5,
                      chunk=64)
        _assert_same_result(res[4], fresh)


# ---------------------------------------------------------------------------
# CRN pairing: the paired-difference stderr the planner eliminates on is
# never worse than the independent-comparison stderr
# ---------------------------------------------------------------------------

class TestPairedVariance:
    @settings(max_examples=8, deadline=None)
    @given(st.sampled_from([False, True]), st.sampled_from([192, 448]),
           st.integers(min_value=0, max_value=2 ** 16))
    def test_paired_stderr_at_most_independent(self, ragged, trials, seed):
        specs = _specs(ragged)
        rs = resumable_sweep(specs, MODEL, N, seed=seed, chunk=64, ks=5,
                             keep_samples=True)
        rs.extend_trials(trials)
        s = rs.samples()
        xa = np.asarray(s["a"], np.float64).ravel()
        xb = np.asarray(s["b"], np.float64).ravel()
        paired = (xa - xb).std(ddof=1)
        indep = np.hypot(xa.std(ddof=1), xb.std(ddof=1))
        # CRN makes the schemes positively correlated (they share every
        # delay draw), so pairing can only shrink the comparison stderr
        # (up to f64 round-off on the variance estimate).
        assert paired <= indep * (1 + 1e-12)


# ---------------------------------------------------------------------------
# rebalance x messages (the gap the planner's grid closes)
# ---------------------------------------------------------------------------

class TestRebalanceMessages:
    def _run(self, m, chunk=64, trials=192):
        C = cyclic_to_matrix(N, 4)
        loads = np.full(N, 2)
        proc = MarkovRegimeProcess(base=MODEL, persistence=0.8)
        sp = adaptive_spec("rb", C, messages=m, loads=loads, rebalance=True)
        return sweep_rounds([sp], proc, N, rounds=3, k=N, trials=trials,
                            seed=1, chunk=chunk)

    def test_budget_at_cap_equals_unlimited_bitwise(self):
        full = self._run(None)
        cap = self._run(4)
        np.testing.assert_array_equal(full.per_round["rb"],
                                      cap.per_round["rb"])

    @pytest.mark.parametrize("m", [1, 2])
    def test_per_trial_trajectories_chunk_invariant(self, m):
        C = cyclic_to_matrix(N, 4)
        loads = np.full(N, 2)
        proc = MarkovRegimeProcess(base=MODEL, persistence=0.8)
        sp = adaptive_spec("rb", C, messages=m, loads=loads, rebalance=True)
        a = trajectory_samples(sp, proc, N, rounds=3, k=N, trials=192,
                               seed=1, chunk=64)
        b = trajectory_samples(sp, proc, N, rounds=3, k=N, trials=192,
                               seed=1, chunk=96)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tighter_budget_is_slower_on_average(self):
        m1 = float(np.mean(self._run(1).per_round["rb"]))
        m2 = float(np.mean(self._run(2).per_round["rb"]))
        mc_ = float(np.mean(self._run(4).per_round["rb"]))
        assert np.isfinite([m1, m2, mc_]).all()
        assert m1 >= m2 >= mc_

    def test_aggregator_accepts_rebalance_with_messages(self):
        # RoundConfig no longer rejects the combination, and the
        # aggregator runs it with the dynamic per-load message remap
        def _agg(m):
            cfg = RoundConfig(n=N, k=N, kind="cs", r=4, loads=(2,) * N,
                              messages=m, adaptive=True, rebalance=True)
            return StragglerAggregator(cfg, MODEL)
        agg = _agg(2)
        ts = [float(agg.round_mask(jax.random.PRNGKey(i))[1])
              for i in range(3)]
        assert np.isfinite(ts).all()
        assert np.isfinite(agg.expected_completion(trials=512))
        # a tighter budget can only slow the round down on average
        assert (_agg(1).expected_completion(trials=512)
                >= _agg(2).expected_completion(trials=512))


# ---------------------------------------------------------------------------
# GridResult.best_cell
# ---------------------------------------------------------------------------

class TestBestCell:
    @pytest.fixture(scope="class")
    def grid(self):
        gs = GridSpec(n=N, families=("cs", "lb", "pc"), loads=(2, 4),
                      trials=256, seed=0)
        return stream_grid(gs.cells(MODEL))

    def test_excludes_lb_and_matches_manual_argmin(self, grid):
        best = grid.best_cell(k=N)
        assert not best["cell"].startswith("lb")
        manual = {}
        for nm, c in grid.cells.items():
            if nm.startswith("lb"):
                continue
            v = np.atleast_1d(list(c["means"].values())[0])
            manual[nm] = float(v[0] if v.shape[-1] == 1 else v[N - 1])
        assert best["cell"] == min(manual, key=manual.get)
        assert best["mean"] == pytest.approx(min(manual.values()))

    def test_tie_report_is_stderr_aware(self, grid):
        # at z=inf every other cell is a tie; at z=0 only exact equals
        loose = grid.best_cell(k=N, z=np.inf)
        tight = grid.best_cell(k=N, z=0.0)
        assert len(loose["ties"]) >= len(tight["ties"])
        assert len(loose["ties"]) == len([nm for nm in grid.cells
                                          if not nm.startswith("lb")]) - 1

    def test_k_validation(self, grid):
        with pytest.raises(ValueError, match="1 <= k"):
            grid.best_cell(k=N + 1)


# ---------------------------------------------------------------------------
# plan(): agreement with the exhaustive grid, invariances, artifact
# ---------------------------------------------------------------------------

GS = GridSpec(n=N, families=("cs", "ss", "lb", "pc"), loads=(2, 4, 8),
              messages=(None, 2), trials=2048, seed=0)


class TestPlan:
    @pytest.fixture(scope="class")
    def result(self):
        return plan(GS, MODEL, k=N, base_trials=256, eta=4)

    def test_matches_exhaustive_argmin_with_fewer_trials(self, result):
        exhaustive = stream_grid(GS.cells(MODEL)).best_cell(k=N)
        assert result.winner == exhaustive["cell"]
        assert result.predicted_mean == pytest.approx(exhaustive["mean"],
                                                      rel=1e-6)
        # this unit grid is tiny (21 cells, n=8, many near-ties), so the
        # bar here is modest; the >= 5x acceptance gate runs against the
        # 64-cell quick grid in benchmarks/planner.py
        assert result.trials_spent * 2 <= result.exhaustive_trials
        assert result.savings >= 2.0

    def test_matched_confidence_at_final_rung(self, result):
        # the winner raced to the full grid budget: same stderr resolution
        # as the exhaustive sweep
        assert result.points[result.winner]["trials"] == GS.trials
        assert result.trajectory[-1]["trials"] == GS.trials

    def test_lb_gap_and_config(self, result):
        assert result.lb_mean is not None
        assert result.lb_gap >= 0.0
        assert result.config is not None
        assert result.config.kind in ("cs", "ss", "ra")
        assert result.config.k == N
        assert result.config_note is None

    def test_point_statuses_cover_every_cell(self, result):
        assert len(result.points) == len(GS.cells(MODEL))
        statuses = {r["status"] for r in result.points.values()}
        assert statuses <= {"won", "survived", "eliminated", "pruned",
                            "excluded"}
        assert sum(1 for r in result.points.values()
                   if r["status"] == "won") == 1
        assert all(r["status"] == "excluded"
                   for nm, r in result.points.items()
                   if nm.startswith("lb"))

    def test_eliminated_points_spent_fewer_trials(self, result):
        for r in result.points.values():
            if r["status"] == "eliminated":
                assert r["trials"] < GS.trials
                assert r["gap"] > 0.0

    def test_elimination_decisions_chunk_invariant(self, result):
        # per-trial samples are bitwise chunk-invariant (CRN fold_in key
        # per trial), so every paired gap — and hence every elimination
        # decision — must be identical under a different chunking
        import dataclasses
        gs2 = dataclasses.replace(GS, chunk=128)
        r2 = plan(gs2, MODEL, k=N, base_trials=256, eta=4)
        assert r2.winner == result.winner
        assert r2.trajectory == result.trajectory
        assert r2.trials_spent == result.trials_spent

    @multidev
    def test_elimination_decisions_device_invariant(self, result):
        r4 = plan(GS, MODEL, k=N, base_trials=256, eta=4,
                  devices=jax.devices()[:4])
        assert r4.winner == result.winner
        assert ([t["survivors"] for t in r4.trajectory]
                == [t["survivors"] for t in result.trajectory])
        assert ([t["eliminated"] for t in r4.trajectory]
                == [t["eliminated"] for t in result.trajectory])

    def test_artifact_round_trip(self, result, tmp_path):
        p = result.save(str(tmp_path / "plan.json"))
        back = PlanResult.load(p)
        assert back.winner == result.winner
        assert back.config == result.config
        assert back.trials_spent == result.trials_spent
        assert back.points[back.winner]["mean"] == \
            pytest.approx(result.points[result.winner]["mean"])

    def test_version_gate(self, tmp_path, result):
        p = tmp_path / "future.json"
        doc = result.to_json()
        doc["version"] = planner_mod.PLAN_FORMAT_VERSION + 1
        p.write_text(json.dumps(doc))
        with pytest.raises(ValueError, match="newer"):
            PlanResult.load(str(p))

    def test_theory_prune_skipped_without_closed_form(self):
        # a process model has no closed-form marginals: every point races
        proc_grid = GridSpec(n=N, families=("cs", "lb"), loads=(2, 4),
                             trials=512, seed=0)
        assert delay_model_pdfs(MarkovRegimeProcess(base=MODEL)) is None
        res = plan(proc_grid, MODEL, k=N, base_trials=256, eta=4,
                   theory_prune=False)
        assert res.meta["theory_pruned"] == 0

    def test_base_trials_must_align_with_chunk(self):
        import dataclasses
        with pytest.raises(ValueError, match="multiple"):
            plan(dataclasses.replace(GS, chunk=96), MODEL, k=N,
                 base_trials=256)


class TestTheoryGuides:
    def test_truncated_gaussian_pdf_normalizes(self):
        pdf = truncated_gaussian_pdf(1e-4, 1e-4, 3e-5)
        t = np.linspace(1e-4 - 3e-5, 1e-4 + 3e-5, 20001)
        trapezoid = getattr(np, "trapezoid", np.trapz)
        assert trapezoid(pdf(t), t) == pytest.approx(1.0, abs=1e-6)

    def test_delay_model_pdfs_scenario1(self):
        pdfs = delay_model_pdfs(MODEL)
        assert pdfs is not None
        pdf1, pdf2, sup1, sup2 = pdfs
        assert sup1 > 0 and sup2 > 0

    def test_lb_guide_below_mc_lower_bound(self):
        pdf1, pdf2, sup1, sup2 = delay_model_pdfs(MODEL)
        guide = operating_point_mean_lb(N, 4, N, pdf1, pdf2,
                                        tmax=1.25 * (4 * sup1 + sup2))
        res = sweep([lb_spec(4)], MODEL, N, trials=4096, seed=0, chunk=512,
                    ks=N)
        # the guide assumes FIFO in-order delivery: a relaxation of the
        # true bound, so it must not exceed the MC estimate by more than
        # sampling noise
        assert guide <= res.at_k("lb", N) * 1.05


# ---------------------------------------------------------------------------
# CLIs
# ---------------------------------------------------------------------------

class TestCLI:
    def test_plan_cli_writes_artifact_and_config(self, tmp_path, capsys):
        out = tmp_path / "plan.json"
        cfg = tmp_path / "cfg.json"
        rc = plan_cli.main([
            "--n", str(N), "--families", "cs", "ss", "lb", "pc",
            "--loads", "2", "4", "8", "--trials", "1024",
            "--base-trials", "256", "--k", str(N),
            "--out", str(out), "--emit-config", str(cfg)])
        assert rc == 0
        text = capsys.readouterr().out
        assert "winner:" in text and "saved" in text
        res = PlanResult.load(str(out))
        assert res.savings > 1.0
        if res.config is not None:
            loaded = RoundConfig.load(cfg)
            assert loaded == res.config

    def test_grid_cli_window_flag_and_meta(self, tmp_path, capsys):
        out = tmp_path / "grid.json"
        rc = grid_cli.main([
            "--n", str(N), "--families", "cs", "lb", "--loads", "2",
            "--trials", "256", "--window", "3", "--out", str(out)])
        assert rc == 0
        text = capsys.readouterr().out
        assert "best:" in text
        from repro.core import GridResult
        res = GridResult.load(str(out))
        assert res.meta["window"] == 3
        assert res.meta["pipeline"] == 3
        assert "cache" in res.meta

    def test_grid_cli_pipeline_alias(self, tmp_path):
        out = tmp_path / "grid.json"
        rc = grid_cli.main([
            "--n", str(N), "--families", "cs", "--loads", "2",
            "--trials", "256", "--pipeline", "4", "--out", str(out)])
        assert rc == 0
        from repro.core import GridResult
        assert GridResult.load(str(out)).meta["window"] == 4
