"""The plain reference of adaptive multi-round scheduling
(``bench/refs/adaptive_round.py``) against the program, and the control
and planted faults that set the upper readings of the rounds cell's
check, at a CPU size.

- On recorded draws the reference reproduces the program's per-trial
  completion times exactly, and its greedy rows and re-balanced loads are
  the program's for the same estimates.
- On independent draws its per-round means agree with the engine's.
- The control puts the reference in the program's place, computed in
  bfloat16 (the configurations state float32): the check has to fail it.
- The faults break the program underneath a whole run (device check
  skipped): the run has to come out not correct.

At the cell's own size, on the chip, the same readings come from

    python3 bench/tests/test_adaptive_ref.py --seeds <s> ... \\
        --control-seeds <s> ... --fault-seeds <s> ...

which prints one JSON line per seed: the sound run's numbers, the
control's, or a fault's.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run as bench_run  # noqa: E402
from bench.refs import adaptive_round as ref  # noqa: E402
from bench.seeds import derive  # noqa: E402
from bench.tests.test_controls import run_cell  # noqa: E402

FIXTURE = ROOT / "bench" / "tests" / "fixture_rounds" / "BENCHMARK.json"
CELL = "mc_rounds.s1-n16-markov.adaptive"


def _cell(workload: str, doc: Path):
    d = json.loads(doc.read_text())
    _, config, traffic, _, _ = bench_run.cell_spec(d, ROOT, workload)
    return config, traffic


def control_readings(traffic: dict, config: dict, seed: int,
                     calls: int) -> dict:
    """The numbers the cell's check compares, with the reference computed
    in bfloat16 in the program's place for ``calls`` window calls."""
    import jax.numpy as jnp
    from bench.drivers.rounds import compare, limited, reference
    chk, trials = traffic["check"], traffic["trials"] * calls
    got = reference(traffic, config, trials, derive(seed, "control"),
                    dtype=jnp.bfloat16)
    want = reference(traffic, config, chk["ref_trials"],
                     derive(seed, "reference"))
    z, dev = compare(got, trials, want, chk["ref_trials"])
    out = limited(z, dev, chk["limits"])
    return {k: c["value"] for k, c in out.items()}


# ------------------------------ planted faults ------------------------------

@contextlib.contextmanager
def _patched(module, name: str, wrap):
    """``module.name`` replaced by ``wrap(original)``; the engine's
    compiled programs are dropped on the way in and out."""
    from repro.core import montecarlo
    saved = getattr(module, name)
    setattr(module, name, wrap(saved))
    montecarlo.clear_cache()
    try:
        yield
    finally:
        setattr(module, name, saved)
        montecarlo.clear_cache()


def _entry(wrap):
    import repro.core as core
    return _patched(core, "sweep_rounds", wrap)


def uncensored():
    """Every worker observed every round, whatever arrived."""
    return _entry(lambda f: lambda *a, **kw: f(
        *a, **dict(kw, censored_feedback=False)))


def identity_greedy():
    """The greedy replaced by the identity assignment: worker i runs row
    i every round."""
    import jax.numpy as jnp
    from repro.core import scheduling
    return _patched(scheduling, "greedy_row_assignment_batch",
                    lambda f: lambda C, est, **kw: jnp.broadcast_to(
                        jnp.arange(est.shape[-1], dtype=jnp.int32),
                        est.shape))


def rebalance_off():
    """Re-balancing returns the initial loads."""
    import jax.numpy as jnp
    from repro.core import scheduling
    return _patched(scheduling, "greedy_load_rebalance_batch",
                    lambda f: lambda est, loads, **kw: jnp.broadcast_to(
                        jnp.asarray(loads, jnp.int32), est.shape))


def persistence_0():
    """The regimes drawn afresh every round."""
    return _entry(lambda f: lambda specs, process, *a, **kw: f(
        specs, dataclasses.replace(process, persistence=0.0), *a, **kw))


def wrong_order_stat():
    """An answer altered where it is produced: the (k-1)-th task closes
    every round."""
    return _entry(lambda f: lambda *a, k, **kw: f(*a, k=k - 1, **kw))


def half_trials():
    """Half of the trials left out, the means taken over the rest."""
    def wrap(f):
        def broken(*a, trials, chunk, **kw):
            half = trials // 2
            return f(*a, trials=half, chunk=min(chunk, half), **kw)
        return broken
    return _entry(wrap)


FAULTS = {f.__name__: f for f in (uncensored, identity_greedy,
                                  rebalance_off, persistence_0,
                                  wrong_order_stat, half_trials)}


# ------------------------- the reference and the program ---------------------

SCHEMES = [
    {"name": "cs3", "family": "cs", "r": 3},
    {"name": "ss3", "family": "ss", "r": 3},
    {"name": "adapt3", "family": "cs", "r": 3, "adaptive": True},
    {"name": "adss3", "family": "ss", "r": 3, "adaptive": True},
    {"name": "rebal3", "family": "cs", "r": 6, "adaptive": True,
     "rebalance": True, "loads": 3},
    {"name": "lb3", "family": "lb", "r": 3},
]


def _program_spec(sc: dict, n: int):
    from bench.drivers.rounds import program_spec
    from repro.core import adaptive_spec, staircase_to_matrix, to_spec
    if sc["family"] != "ss":
        return program_spec(sc, n)
    C = staircase_to_matrix(n, int(sc["r"]))
    return adaptive_spec(sc["name"], C) if sc.get("adaptive") else \
        to_spec(sc["name"], C)


@pytest.fixture(scope="module")
def cell():
    """The cell's configuration and the fixture's CPU-sized traffic."""
    return _cell("fixture.rounds", FIXTURE)


@pytest.fixture(scope="module")
def small(cell):
    """The cell's cluster overlay on eight workers."""
    return dict(cell[0], n=8)


def test_machine_scales_are_the_programs():
    from repro.core import heterogeneous_scales
    for n, spread, seed in ((16, 3.0, 1), (8, 2.0, 0), (5, 1.0, 3)):
        np.testing.assert_allclose(ref.machine_scales(n, spread, seed),
                                   heterogeneous_scales(n, spread, seed),
                                   rtol=1e-15)


@pytest.mark.parametrize("censored", [True, False])
@pytest.mark.parametrize("scheme", SCHEMES, ids=lambda s: s["name"])
def test_same_draws_same_rounds(small, scheme, censored):
    """Fed the delays a ``record_trace=True`` run recorded, the reference
    reproduces every trial's completion time of every round; its greedy
    rows and re-balanced loads are the program's for the estimates it
    assigned each round from."""
    import jax.numpy as jnp
    from bench.drivers.rounds import program_process
    from repro.core import (greedy_load_rebalance_batch,
                            greedy_row_assignment_batch, trajectory_samples)
    config = small
    n, k, rounds = int(config["n"]), 6, 5
    times, trace = trajectory_samples(
        _program_spec(scheme, n), program_process(config), n, rounds=rounds,
        k=k, trials=1536, chunk=512, seed=11, record_trace=True,
        censored_feedback=censored)
    got = ref.replay([scheme], trace.T1, trace.T2, n, k,
                     censored=censored)[scheme["name"]]
    np.testing.assert_array_equal(got["times"], np.asarray(times))
    if not scheme.get("adaptive"):
        return
    C = ref._setup([scheme], n, 0.5)[0]["C"]
    est = jnp.asarray(got["est"].reshape(-1, n))
    rows = np.asarray(greedy_row_assignment_batch(C, est, gamma=0.5))
    np.testing.assert_array_equal(got["rows"].reshape(-1, n), rows)
    if scheme.get("rebalance"):
        l0 = np.full(n, scheme["loads"])
        want = np.asarray(greedy_load_rebalance_batch(
            est, l0, r_max=int(scheme["r"]), min_load=1))
        np.testing.assert_array_equal(
            np.asarray(ref.rebalanced_loads(est, l0, int(scheme["r"]))),
            want)


def test_rebalance_keeps_unobserved_loads_from_the_highest_index():
    """Observed workers at the cap cannot hold the total: the unobserved
    ones keep their initial loads from the highest worker index down, as
    the program's makespan descent leaves them."""
    import jax.numpy as jnp
    from repro.core import greedy_load_rebalance_batch
    inf = np.inf
    est = jnp.asarray([[2e-4, inf, inf, inf, inf, inf],
                       [inf] * 6,
                       [1e-4, 3e-4, inf, 2e-4, inf, 5e-4]], jnp.float32)
    l0 = np.full(6, 4)
    got = np.asarray(ref.rebalanced_loads(est, l0, 6))
    want = np.asarray(greedy_load_rebalance_batch(est, l0, r_max=6,
                                                  min_load=1))
    np.testing.assert_array_equal(got, want)
    assert got.tolist()[0] == [6, 2, 4, 4, 4, 4]


def test_means_agree_with_the_engine(small):
    from bench.drivers.rounds import program_process
    from repro.core import sweep_rounds
    config = small
    n, k, rounds, trials = int(config["n"]), 6, 5, 8192
    res = sweep_rounds([_program_spec(s, n) for s in SCHEMES],
                       program_process(config), n, rounds=rounds, k=k,
                       trials=trials, chunk=4096, seed=4,
                       censored_feedback=True)
    want = ref.round_means(SCHEMES, config["delays"], config["process"], n,
                           k, rounds, 16384, seed=9, block=8192)
    for s in SCHEMES:
        m, se = res.per_round[s["name"]], res.stderr[s["name"]]
        rm, rse = want[s["name"]]
        z = np.abs(m - rm) / np.hypot(se, rse)
        assert z.max() <= 5, (s["name"], z)
        # a slow regime makes the completion time heavy-tailed, and its
        # spread is itself noisy: at these trials it reads up to 16% apart
        np.testing.assert_allclose(se * math.sqrt(trials),
                                   rse * math.sqrt(16384), rtol=0.25)


def test_adaptive_beats_static_in_the_reference(small):
    """What the cell exercises: on this cluster the reference puts the
    greedy below static CS and re-balancing below both, every round after
    the first few."""
    config = small
    n = int(config["n"])
    want = ref.round_means(SCHEMES, config["delays"], config["process"], n,
                           6, 5, 8192, seed=3, block=8192)
    cs, ad, rb = (want[nm][0][2:] for nm in ("cs3", "adapt3", "rebal3"))
    assert (ad < cs).all() and (rb < ad).all(), (cs, ad, rb)


# ------------------------------ the check's gates ----------------------------

def test_control_fails(cell):
    config, traffic = cell
    got = control_readings(traffic, config, seed=5, calls=2)
    lim = traffic["check"]["limits"]
    assert any(got[k] > lim[k] for k in lim), got


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault):
    with FAULTS[fault]():
        out = run_cell("fixture.rounds", 7, 1.0, FIXTURE)
    assert out["correct"] is False, out["checks"]


def test_sound_run_is_correct():
    out = run_cell("fixture.rounds", 2 ** 31 + 9, 1.0, FIXTURE)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


# ------------------------------- at cell size -------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=sorted(FAULTS))
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control-calls", type=int, default=20)
    args = ap.parse_args(argv)
    config, traffic = _cell(args.workload, bench_run.DOC)

    def readings(out):
        return {k: c["value"] for k, c in out["checks"].items()}

    for s in args.seeds:
        out = run_cell(args.workload, s, args.seconds, bench_run.DOC)
        print(json.dumps({"seed": s, "sound": readings(out),
                          "calls": out["attempted"]}), flush=True)
    for s in args.control_seeds:
        print(json.dumps({"seed": s, "control": control_readings(
            traffic, config, s, args.control_calls)}), flush=True)
    for name in args.faults:
        with FAULTS[name]():
            for s in args.fault_seeds:
                out = run_cell(args.workload, s, args.seconds,
                               bench_run.DOC)
                print(json.dumps({"seed": s, name: readings(out)}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
