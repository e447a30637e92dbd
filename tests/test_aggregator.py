"""StragglerAggregator + RoundConfig property tests, including the
round-aware cluster state and adaptive scheduling paths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (RoundConfig, StragglerAggregator, ec2_cluster,
                        scenario1, validate_to_matrix)


class TestRoundConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            RoundConfig(n=4, r=2, k=5)
        with pytest.raises(ValueError):
            RoundConfig(n=4, r=5, k=2)
        with pytest.raises(ValueError):
            RoundConfig(n=4, r=0, k=2)

    def test_to_matrix_schedules(self):
        for sched in ("cs", "ss", "block"):
            C = RoundConfig(n=6, r=3, k=4, kind=sched).to_matrix()
            assert C.shape == (6, 3)
        C = RoundConfig(n=6, r=6, k=4, kind="ra").to_matrix()
        assert C.shape == (6, 6)


class TestAggregator:
    def test_round_mask_and_combine(self):
        spec = RoundConfig(n=4, r=2, k=3, kind="cs")
        agg = StragglerAggregator(spec, scenario1())
        w, t = agg.round_mask(jax.random.PRNGKey(0))
        assert w.shape == (4, 2)
        assert np.isclose(float(w.sum()), 3.0, atol=1e-5)
        grads = {"a": jnp.ones((4, 2, 3)), "b": jnp.ones((4, 2))}
        out = agg.combine(grads, w)
        # sum of weights / k = 1 -> combined grad of all-ones is 1
        np.testing.assert_allclose(np.asarray(out["a"]), 1.0, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out["b"]), 1.0, rtol=1e-5)

    def test_expected_completion_positive_and_orders(self):
        m = scenario1()
        fast = StragglerAggregator(RoundConfig(n=8, r=4, k=4, kind="ss"), m)
        slow = StragglerAggregator(RoundConfig(n=8, r=4, k=8, kind="ss"), m)
        key = jax.random.PRNGKey(1)
        tf = fast.expected_completion(key)
        ts = slow.expected_completion(key)
        assert 0 < tf < ts

    def test_cluster_state_persists_across_rounds(self):
        """A persistent-straggler process threads its state through
        round_mask calls: identical keys give different delays on
        consecutive rounds (state advanced), and straggling workers stay
        slow — consecutive per-round completion times are correlated."""
        spec = RoundConfig(n=8, r=2, k=6, kind="cs")
        proc = ec2_cluster(8, spread=3.0, p_slow=0.3, persistence=0.98,
                           slow=20.0)
        agg = StragglerAggregator(spec, proc)
        key = jax.random.PRNGKey(0)
        _, t_a = agg.round_mask(key)
        # two aggregators, same init key -> same realization (determinism)
        agg2 = StragglerAggregator(spec, proc)
        _, t_a2 = agg2.round_mask(key)
        assert float(t_a) == float(t_a2)
        # regime state is carried and evolves across rounds
        states = []
        ts = []
        for i in range(60):
            ts.append(float(agg2.round_mask(jax.random.PRNGKey(i))[1]))
            states.append(np.asarray(agg2._state[0]))
        assert any(not np.array_equal(states[i], states[i + 1])
                   for i in range(len(states) - 1))
        # persistence: consecutive rounds' completion times correlate
        a, b = np.array(ts[:-1]), np.array(ts[1:])
        assert np.corrcoef(a, b)[0, 1] > 0.2

    def test_adaptive_round_api(self):
        spec = RoundConfig(n=8, r=2, k=6, kind="cs", adaptive=True)
        proc = ec2_cluster(8, spread=3.0, persistence=0.95, slow=10.0)
        agg = StragglerAggregator(spec, proc)
        for i in range(4):
            C = agg.current_matrix()
            validate_to_matrix(C, 8)
            # rows are a permutation of the base schedule's rows
            assert sorted(map(tuple, C.tolist())) == \
                sorted(map(tuple, spec.to_matrix().tolist()))
            w, t = agg.round_mask(jax.random.PRNGKey(i))
            assert np.isclose(float(w.sum()), spec.k, atol=1e-4)
            assert float(t) > 0
        assert agg.scheduler.est is not None     # feedback accumulated

    def test_expected_completion_routes_through_engine(self):
        spec = RoundConfig(n=8, r=4, k=6, kind="ss")
        proc = ec2_cluster(8, spread=2.0, persistence=0.9)
        agg = StragglerAggregator(spec, proc)
        t = agg.expected_completion(trials=8192)
        t2 = agg.expected_completion(trials=512, rounds=3)
        assert 0 < t and 0 < t2
        ad = StragglerAggregator(
            dataclasses.replace(spec, adaptive=True), proc)
        t_ad = ad.expected_completion(trials=8192)
        # adaptive helps here: the plain reference of bench/refs/
        # adaptive_round.py puts the gap at 2.98e-6 s (about 41 standard
        # errors at 131,072 trials).  Both estimates share their draws;
        # at 8,192 trials the gap is about ten standard errors of the
        # paired difference (at 512 it is 2.6, and seed 0 reads it
        # reversed).
        assert 0 < t_ad < t

    @settings(deadline=None, max_examples=20)
    @given(st.integers(2, 8), st.data())
    def test_property_combine_unbiased_weighting(self, n, data):
        r = data.draw(st.integers(1, n))
        k = data.draw(st.integers(1, n))
        sched = data.draw(st.sampled_from(["cs", "ss"]))
        spec = RoundConfig(n=n, r=r, k=k, kind=sched)
        agg = StragglerAggregator(spec, scenario1())
        w, _ = agg.round_mask(jax.random.PRNGKey(data.draw(
            st.integers(0, 2**16))))
        # combine of per-slot gradient g=1 equals (sum w)/k = 1 exactly
        g = {"x": jnp.ones((n, r, 5))}
        out = agg.combine(g, w)
        np.testing.assert_allclose(np.asarray(out["x"]), 1.0, rtol=1e-4)
