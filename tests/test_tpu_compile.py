"""The main path's Pallas kernels compile for a TPU v5e at real sizes, and
the engine's single-k order statistics compile without a narrow sort.

Nothing runs: each test compiles for one chip of a described (not
attached) ``v5e:2x2`` topology, or for its four chips under the
engine's trial sharding, and checks that the program holds the Mosaic
kernel (``tpu_custom_call``), not an interpreted loop.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the worker given this file loads
the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import montecarlo as mc
from repro.core import scenario1
from repro.kernels.ops import gram_matvec, greedy_assign


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """JAX's persistent compilation cache off: an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo, no_cache):
    """(split, replicated) shardings on the topology's 1-D trial mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding import TRIAL_AXIS, trial_mesh
    mesh = trial_mesh(tuple(topo.devices))
    return NamedSharding(mesh, P(TRIAL_AXIS)), NamedSharding(mesh, P())


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,B", [(16, 4096), (15, 20000), (64, 4096)])
def test_greedy_assign_compiles(one_chip, n, B):
    fn = jax.jit(lambda W, order, epick, need: greedy_assign(
        W, order, epick, need, interpret=False))
    compiled = fn.lower(_spec((n, n), jnp.float32, one_chip),
                        _spec((B, n), jnp.int32, one_chip),
                        _spec((B, n), jnp.float32, one_chip),
                        _spec((B, n), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%greedy_assign" in compiled.as_text()     # the kernel's name


@pytest.mark.parametrize("d,b", [(400, 60), (4096, 4096)])
def test_gram_matvec_compiles(one_chip, d, b):
    fn = jax.jit(lambda X, th: gram_matvec(X, th, interpret=False))
    compiled = fn.lower(_spec((d, b), jnp.float32, one_chip),
                        _spec((d,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%gram_xt_theta" in compiled.as_text()     # the kernels' names
    assert "%gram_x_u" in compiled.as_text()


def test_greedy_assign_compiles_trial_sharded(topo, four_chips):
    # the kernel under the engine's trial sharding (devices=4): XLA cannot
    # partition a Mosaic call, so each chip must run it on its own block
    from repro.sharding import shard_trials
    split, rep = four_chips
    n, B = 16, 4 * 2048
    fn = shard_trials(lambda W, order, epick: greedy_assign(
        W, order, epick, interpret=False), topo.devices, replicated=(0,))
    compiled = fn.lower(_spec((n, n), jnp.float32, rep),
                        _spec((B, n), jnp.int32, split),
                        _spec((B, n), jnp.float32, split)).compile()
    assert len(topo.devices) == 4
    assert "tpu_custom_call" in compiled.as_text()
    assert "%greedy_assign" in compiled.as_text()


def test_sweep_selection_has_no_narrow_sort(one_chip, sweep11_specs):
    # the benchmark's sweep11 mix at k = 16: the five TO schemes' and the
    # two pc schemes' order statistics of 16-wide axes are rank counts on
    # the chip's compile, while the lb and pcmm windows (256 wide) keep
    # their sorts
    n, chunk = 16, 1024
    sig, params, _ = mc._eval_layout(tuple(sweep11_specs), n, 16, 16)
    fn = jax.jit(mc._build_stats_fn(sig, scenario1()))
    compiled = fn.lower(
        _spec((chunk, 2), jnp.uint32, one_chip),
        {k: _spec(np.shape(v), np.asarray(v).dtype, one_chip)
         for k, v in params.items()}).compile()
    widths = []
    for line in compiled.as_text().splitlines():
        if " sort(" in line:
            dims = re.search(r"\[([0-9,]+)\]", line).group(1).split(",")
            (axis,) = re.search(r"dimensions=\{(\d+)\}", line).groups()
            widths.append(int(dims[int(axis)]))
    assert sorted(widths) == [256, 256]


@pytest.mark.parametrize("chunk,rounds", [(16384, 20)])
def test_rounds_program_compiles(one_chip, monkeypatch, chunk, rounds):
    # the benchmark's adaptive20 mix on the s1-n16-markov cluster: one
    # chunk of the rounds scan compiles for the chip, and both adaptive
    # schemes' greedy is the Mosaic kernel inside the engine.greedy scope;
    # the re-balance's descent sits in a conditional, not a select of both
    # branches, beside the closed form's one sort (which the tie counter
    # shares)
    from repro.core import (MarkovRegimeProcess, adaptive_spec,
                            cyclic_to_matrix, heterogeneous_scales, lb_spec,
                            to_spec)
    from repro.kernels import gram_matvec
    monkeypatch.setattr(gram_matvec, "default_interpret", lambda **kw: False)
    n, k = 16, 12
    specs = mc._check_rounds_args(
        [to_spec("cs4", cyclic_to_matrix(n, 4)),
         adaptive_spec("adapt4", cyclic_to_matrix(n, 4)),
         adaptive_spec("rebal4", cyclic_to_matrix(n, 8), loads=(4,) * n,
                       rebalance=True),
         lb_spec(4, name="lb4")], n, k, rounds)
    process = MarkovRegimeProcess(
        base=scenario1(), worker_scale=heterogeneous_scales(n, 3.0, 1),
        p_slow=0.25, persistence=0.95, slow=8.0)
    fn = mc._build_rounds_fn(specs, process, n, 8, k, rounds, 0.7, 0.5,
                             True, greedy_impl="kernel")
    text = jax.jit(fn).lower(_spec((chunk, 2), jnp.uint32, one_chip),
                             _spec((chunk,), jnp.int32, one_chip)
                             ).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "%greedy_assign" in ln]
    assert len(calls) == 2
    assert all("engine.greedy" in ln for ln in calls)
    assert "engine.rebalance" in text
    rebalance = [ln for ln in text.splitlines() if "engine.rebalance" in ln]
    assert len([ln for ln in rebalance if " conditional(" in ln]) == 1
    assert len([ln for ln in rebalance if " sort(" in ln]) == 1
