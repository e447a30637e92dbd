"""The plain single-round reference (``bench/refs/round_mc.py``) against
the program at a tiny size: its schedules are the program's, and its
means agree with the engine's within their standard errors."""
import math

import numpy as np
import pytest

from bench.refs import round_mc

DELAYS = dict(mu1=1e-4, sigma1=1e-4, a1=3e-5, mu2=5e-4, sigma2=2e-4, a2=2e-4)


@pytest.mark.parametrize("n,r", [(6, 3), (16, 4), (16, 16)])
def test_schedules_are_the_programs(n, r):
    from repro.core import (cyclic_to_matrix, random_assignment_to_matrix,
                            staircase_to_matrix)
    assert np.array_equal(round_mc.cyclic(n, r), cyclic_to_matrix(n, r))
    assert np.array_equal(round_mc.staircase(n, r), staircase_to_matrix(n, r))
    for seed in (0, 101):
        assert np.array_equal(round_mc.random_assignment(n, seed),
                              random_assignment_to_matrix(n, seed=seed))


@pytest.mark.parametrize("r,m", [(4, 4), (4, 2), (5, 2), (7, 3), (3, 1)])
def test_message_layout_is_the_programs(r, m):
    from repro.core.montecarlo import message_slot_map
    close, msg = round_mc.message_layout(r, m)
    assert np.array_equal(close, message_slot_map(r, m))
    assert msg[0] == 0 and msg[-1] == m - 1


SCHEMES = [
    {"name": "cs3", "family": "cs", "r": 3},
    {"name": "ss3m2", "family": "ss", "r": 3, "messages": 2},
    {"name": "cs2eps", "family": "cs", "r": 2, "comm_eps": 2e-4},
    {"name": "ra6", "family": "ra", "r": 6, "seed": 3},
    {"name": "lb3m2", "family": "lb", "r": 3, "messages": 2, "comm_eps": 1e-4},
    {"name": "pc3", "family": "pc", "r": 3},
    {"name": "pcmm3", "family": "pcmm", "r": 3},
    {"name": "pcmm6m2", "family": "pcmm", "r": 6, "messages": 2},
]


def test_means_agree_with_the_engine():
    from bench.drivers.sweep import program_spec
    from repro.core import TruncatedGaussianDelays, sweep
    n, k, trials = 6, 5, 32768
    res = sweep([program_spec(s, n) for s in SCHEMES],
                TruncatedGaussianDelays(**DELAYS), n, trials=trials,
                chunk=8192, ks=k, seed=4)
    ref = round_mc.round_means(SCHEMES, DELAYS, n, k, trials, seed=9,
                               block=8192)
    for s in SCHEMES:
        m = res.at_k(s["name"], k)
        se = float(np.ravel(res.stderr[s["name"]])[-1])
        rm, rse = ref[s["name"]]
        assert abs(m - rm) <= 5 * math.hypot(se, rse), (s["name"], m, rm)
        assert rse == pytest.approx(se, rel=0.1)


def test_bfloat16_reads_apart():
    """The control's precision moves the means by many standard errors on
    paired draws (the same seed)."""
    import jax.numpy as jnp
    n, k, trials = 6, 5, 16384
    f32 = round_mc.round_means(SCHEMES, DELAYS, n, k, trials, seed=1)
    bf16 = round_mc.round_means(SCHEMES, DELAYS, n, k, trials, seed=1,
                                dtype=jnp.bfloat16)
    z = [abs(f32[s["name"]][0] - bf16[s["name"]][0]) / f32[s["name"]][1]
         for s in SCHEMES]
    assert max(z) > 5, z
