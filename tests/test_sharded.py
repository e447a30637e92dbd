"""Device-sharded Monte-Carlo sweeps: bit-exactness vs the single-device
path, shard layout math, chunk validation, and evaluator-cache hygiene.

The bit-exactness classes need >= 4 devices; CI forces them on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  On a plain
single-device run those classes skip and only the device-free layout /
validation tests execute.
"""
import jax
import numpy as np
import pytest

from repro.core import (adaptive_spec, clear_cache, lb_spec, scenario1,
                        to_spec)
from repro.core import montecarlo as mc
from repro.core.cluster import MarkovRegimeProcess
from repro.core.montecarlo import (completion_samples, sweep, sweep_rounds,
                                   trajectory_samples)
from repro.core.scheduling import cyclic_to_matrix, staircase_to_matrix
from repro.sharding import trial_devices, trial_mesh, TRIAL_AXIS

multidev = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")

N = 8
C_CYC = cyclic_to_matrix(N, 3)
C_SS = staircase_to_matrix(N, 3)


def _specs():
    return [to_spec("cyc", C_CYC), to_spec("ss", C_SS), lb_spec(3, "lb"),
            adaptive_spec("adapt", C_CYC)]


def _markov():
    return MarkovRegimeProcess(base=scenario1(), p_slow=0.2, persistence=0.9)


def tree_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# device-free: shard layout + argument validation
# ---------------------------------------------------------------------------

class TestShardLayout:
    def test_chunk_decomposition_is_device_invariant(self):
        devs = jax.devices()
        _, nc_pad, padded = mc._shard_layout(100, 10, devs[:1])
        assert (nc_pad, padded) == (10, 100)

    def test_padding_rounds_up_to_devices(self):
        # synthetic 4-"device" tuple: layout math never touches the devices
        devs = tuple(jax.devices()) * 4
        used, nc_pad, padded = mc._shard_layout(403, 50, devs[:4])
        assert len(used) == 4
        assert nc_pad == 12 and padded == 600   # ceil(9/4)*4 chunks
        used, nc_pad, padded = mc._shard_layout(96, 7, devs[:4])
        assert nc_pad == 16 and padded == 112   # ceil(14/4)*4

    def test_fewer_chunks_than_devices(self):
        devs = tuple(jax.devices()) * 4
        used, nc_pad, padded = mc._shard_layout(10, 10, devs[:4])
        assert len(used) == 1 and nc_pad == 1 and padded == 10

    def test_trial_devices_forms(self):
        all_devs = tuple(jax.devices())
        assert trial_devices(None) == all_devs
        assert trial_devices(1) == all_devs[:1]
        assert trial_devices(list(all_devs)) == all_devs
        with pytest.raises(ValueError, match="devices"):
            trial_devices(0)
        with pytest.raises(ValueError, match="devices"):
            trial_devices(len(all_devs) + 1)
        with pytest.raises(ValueError, match="devices"):
            trial_devices([])

    def test_trial_mesh_axis(self):
        mesh = trial_mesh(jax.devices()[:1])
        assert mesh.axis_names == (TRIAL_AXIS,)


class TestChunkValidation:
    """The canonical ``_normalize_chunk`` raises a ValueError naming the
    argument instead of silently clamping (satellite fix)."""

    def test_chunk_exceeds_trials_named(self):
        with pytest.raises(ValueError, match=r"chunk \(50\) exceeds trials"):
            sweep(_specs()[:2], scenario1(), N, trials=20, chunk=50)

    def test_rounds_chunk_exceeds_trials_named(self):
        with pytest.raises(ValueError, match=r"chunk \(9\) exceeds trials"):
            sweep_rounds(_specs()[:1], _markov(), N, rounds=2, k=6,
                         trials=8, chunk=9)

    def test_chunk_below_one(self):
        with pytest.raises(ValueError, match="chunk"):
            sweep(_specs()[:2], scenario1(), N, trials=20, chunk=0)

    def test_chunk_none_is_one_chunk(self):
        assert mc._normalize_chunk(17, None) == 17
        assert mc._normalize_chunk(17, 5) == 5


# ---------------------------------------------------------------------------
# forced multi-device mesh: bit-exactness vs single device
# ---------------------------------------------------------------------------

@multidev
class TestShardedBitExact:
    @pytest.mark.parametrize("trials,chunk", [(200, 25), (403, 50), (96, 7)])
    def test_sweep_stats(self, trials, chunk):
        r1 = sweep(_specs()[:3], scenario1(), N, trials=trials, seed=3,
                   chunk=chunk, devices=1)
        r4 = sweep(_specs()[:3], scenario1(), N, trials=trials, seed=3,
                   chunk=chunk, devices=4)
        tree_equal(r1.means, r4.means)
        tree_equal(r1.stderr, r4.stderr)

    def test_sweep_per_trial_samples(self):
        s1 = completion_samples(_specs()[0], scenario1(), N, trials=96,
                                seed=3, chunk=7, k=6, devices=1)
        s4 = completion_samples(_specs()[0], scenario1(), N, trials=96,
                                seed=3, chunk=7, k=6, devices=4)
        tree_equal(s1, s4)

    def test_sweep_tau_and_message_budget(self):
        from repro.core.montecarlo import tau_spec
        specs = [to_spec("cs_m2", C_CYC, messages=2),
                 tau_spec("tau", C_SS),
                 to_spec("ragged", cyclic_to_matrix(N, loads=[3, 1, 2, 3,
                                                              1, 3, 2, 1]))]
        r1 = sweep(specs, scenario1(), N, trials=150, seed=2, chunk=25,
                   devices=1)
        r4 = sweep(specs, scenario1(), N, trials=150, seed=2, chunk=25,
                   devices=4)
        tree_equal(r1.means, r4.means)
        tree_equal(r1.stderr, r4.stderr)

    def test_rounds_rebalance_and_faults(self):
        from repro.core.cluster import make_scenario
        specs = [to_spec("cs", C_CYC), lb_spec(3, "lb"),
                 adaptive_spec("rebal", cyclic_to_matrix(N, 6),
                               rebalance=True, loads=[3] * N)]
        proc = make_scenario("preemption", _markov(), N)
        kw = dict(rounds=3, k=6, trials=120, seed=11, chunk=20,
                  deadline=0.004, deadline_policy="close_partial")
        r1 = sweep_rounds(specs, proc, N, devices=1, **kw)
        r4 = sweep_rounds(specs, proc, N, devices=4, **kw)
        tree_equal(r1.per_round, r4.per_round)
        tree_equal(r1.wallclock, r4.wallclock)
        tree_equal(r1.degradation, r4.degradation)

    @pytest.mark.parametrize("kw", [
        dict(),
        dict(censored_feedback=True),
        dict(deadline=0.004, deadline_policy="close_partial"),
        dict(deadline=0.004, censored_feedback=True,
             deadline_policy="reissue"),
    ], ids=["plain", "censored", "close_partial", "censored_reissue"])
    @pytest.mark.parametrize("trials", [120, 121])
    def test_sweep_rounds(self, kw, trials):
        args = (_specs(), _markov(), N)
        kw2 = dict(rounds=3, k=6, trials=trials, seed=7, chunk=20, **kw)
        r1 = sweep_rounds(*args, devices=1, **kw2)
        r4 = sweep_rounds(*args, devices=4, **kw2)
        tree_equal(r1.per_round, r4.per_round)
        tree_equal(r1.stderr, r4.stderr)
        tree_equal(r1.wallclock, r4.wallclock)
        tree_equal(r1.wallclock_stderr, r4.wallclock_stderr)
        if r1.degradation or r4.degradation:
            tree_equal(r1.degradation, r4.degradation)

    def test_sweep_rounds_vector_mean_process(self):
        # ec2_cluster: per-worker base means under the regime chain
        from repro.core import ec2_cluster
        specs = _specs() + [adaptive_spec("rebal", cyclic_to_matrix(N, 6),
                                          rebalance=True, loads=[3] * N)]
        kw = dict(rounds=4, k=6, trials=160, seed=0, chunk=40,
                  censored_feedback=True)
        proc = ec2_cluster(N, persistence=0.95)
        r1 = sweep_rounds(specs, proc, N, devices=1, **kw)
        r4 = sweep_rounds(specs, proc, N, devices=4, **kw)
        tree_equal(r1.per_round, r4.per_round)
        tree_equal(r1.stderr, r4.stderr)
        tree_equal(r1.wallclock, r4.wallclock)

    def test_trajectory_samples(self):
        kw = dict(rounds=3, k=6, trials=61, seed=5, chunk=10, deadline=0.004)
        t1 = trajectory_samples(_specs()[3], _markov(), N, devices=1, **kw)
        t4 = trajectory_samples(_specs()[3], _markov(), N, devices=4, **kw)
        tree_equal(t1, t4)

    def test_greedy_impls_agree_sharded(self):
        kw = dict(rounds=3, k=6, trials=80, seed=9, chunk=20, devices=4)
        rs = sweep_rounds(_specs(), _markov(), N, greedy_impl="scan", **kw)
        rk = sweep_rounds(_specs(), _markov(), N, greedy_impl="kernel", **kw)
        tree_equal(rs.per_round, rk.per_round)
        tree_equal(rs.wallclock, rk.wallclock)

    def test_devices_sequence_matches_int(self):
        devs = jax.devices()[:4]
        kw = dict(trials=100, seed=1, chunk=25)
        ra = sweep(_specs()[:2], scenario1(), N, devices=4, **kw)
        rb = sweep(_specs()[:2], scenario1(), N, devices=devs, **kw)
        tree_equal(ra.means, rb.means)


# ---------------------------------------------------------------------------
# evaluator-cache hygiene (satellite: no retrace, clear_cache drops all)
# ---------------------------------------------------------------------------

@multidev
class TestShardedCache:
    def test_repeated_sweeps_do_not_rebuild(self, monkeypatch):
        clear_cache()
        calls = []
        orig = mc.shard_trials
        monkeypatch.setattr(
            mc, "shard_trials",
            lambda fn, devs, **kw: calls.append(1) or orig(fn, devs, **kw))
        kw = dict(trials=100, seed=1, chunk=25, devices=4)
        sweep(_specs()[:2], scenario1(), N, **kw)
        n_first = len(calls)
        assert n_first > 0
        for _ in range(3):
            sweep(_specs()[:2], scenario1(), N, **kw)
        assert len(calls) == n_first    # cache hit: no new sharded wrap

    def test_cache_keyed_by_device_tuple(self):
        clear_cache()
        kw = dict(trials=100, seed=1, chunk=25)
        sweep(_specs()[:2], scenario1(), N, devices=1, **kw)
        n1 = len(mc._EXEC_CACHE)
        sweep(_specs()[:2], scenario1(), N, devices=4, **kw)
        assert len(mc._EXEC_CACHE) == n1 + 1   # distinct mesh, distinct entry

    def test_clear_cache_drops_sharded_entries(self):
        kw = dict(trials=100, seed=1, chunk=25, devices=4)
        sweep(_specs()[:2], scenario1(), N, **kw)
        sweep_rounds(_specs()[:1], _markov(), N, rounds=2, k=6, **kw)
        assert mc._EXEC_CACHE and mc._ROUNDS_CACHE
        clear_cache()
        assert not mc._EXEC_CACHE and not mc._ROUNDS_CACHE
