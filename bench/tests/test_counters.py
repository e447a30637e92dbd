"""The benchmark's own counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.counters import CompileCounter, round_ops_per_eval


@pytest.mark.parametrize("family,n,r,want", [
    ("cs", 16, 4, 2 * 64 + 64 + 16 * 4),       # sums, copies' min, sort of 16
    ("ra", 16, 16, 2 * 256 + 256 + 16 * 4),
    ("lb", 16, 4, 2 * 64 + 64 * 6),            # sort of the 64 arrivals
    ("pcmm", 16, 16, 2 * 256 + 256 * 8),
    ("pc", 16, 4, 2 * 64 + 16 * 4),            # sort of the 16 messages
    ("cs", 6, 3, 2 * 18 + 18 + 6 * 3),
])
def test_round_ops_per_eval(family, n, r, want):
    assert round_ops_per_eval(family, n, r) == want


def test_unknown_family():
    with pytest.raises(ValueError):
        round_ops_per_eval("xx", 4, 2)


def test_compile_counter_counts_new_programs_only():
    c = CompileCounter()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)
    f(np.ones(7, np.float32)).block_until_ready()
    first = c.compiles
    assert first >= 1
    f(np.ones(7, np.float32)).block_until_ready()
    assert c.compiles == first                  # same shape: no program
    f(np.ones(9, np.float32)).block_until_ready()
    assert c.compiles == first + 1              # a new shape: one more
