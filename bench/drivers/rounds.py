"""Driver: repeated multi-round sweeps through ``repro.core.sweep_rounds``.

Traffic keys: ``k`` (each round's target), ``rounds``, ``trials`` and
``chunk`` of one call, ``censored_feedback``, ``feedback_beta``,
``coverage_gamma``, ``schemes`` (``{"name", "family", "r"}``: family
``cs`` or ``lb``; a ``cs`` scheme with ``"adaptive": true`` re-assigns
its rows every round, and with ``"rebalance": true`` and ``"loads"`` (the
initial load of every worker, ``r`` being the cap) also moves whole slots
between workers), and ``check`` (the reference's ``ref_trials`` and the
``limits``).  The configuration gives ``n``, the
truncated-Gaussian base ``delays`` (eq. 66) and the straggler
``process`` overlay (``kind`` ``markov``).

One call is one ``sweep_rounds`` of every scheme over ``trials`` fresh
trials from a seed of its own; its work is trials x schemes x rounds
evaluations.  After the window, every call's means are pooled (a call's
trials are too few to tell a lower precision apart) and compared, per
scheme and per round, with the plain Monte-Carlo of
``bench/refs/adaptive_round.py`` on independent draws of ``ref_trials``
trials:

- ``z_max``: the largest |engine mean - reference mean| in combined
  standard errors;
- ``z_mean``: the mean of those differences, signed, in absolute value:
  a bias that moves every scheme and round alike, such as a lower
  precision, moves it though no one comparison stands out;
- ``se_ratio_dev``: the largest |engine spread / reference spread - 1|,
  each spread being a standard error times the square root of its trials.
"""
from __future__ import annotations

import math

import numpy as np

from bench.drivers.sweep import delay_model
from bench.seeds import derive


def program_process(config: dict):
    """The program's ``DelayProcess`` of a configuration."""
    from repro.core import MarkovRegimeProcess, heterogeneous_scales
    p, n = config["process"], int(config["n"])
    if p["kind"] != "markov":
        raise ValueError(f"unknown process kind {p['kind']!r}")
    return MarkovRegimeProcess(
        base=delay_model(config["delays"]),
        worker_scale=heterogeneous_scales(n, float(p["spread"]),
                                          int(p["scale_seed"])),
        p_slow=float(p["p_slow"]), persistence=float(p["persistence"]),
        slow=float(p["slow"]))


def program_spec(scheme: dict, n: int):
    """The program's ``SchemeSpec`` of one traffic scheme."""
    from repro.core import adaptive_spec, cyclic_to_matrix, lb_spec, to_spec
    fam, name, r = scheme["family"], scheme["name"], int(scheme["r"])
    if fam == "lb":
        return lb_spec(r, name=name)
    if fam != "cs":
        raise ValueError(f"unknown family {fam!r}")
    C = cyclic_to_matrix(n, r)
    if scheme.get("rebalance"):
        return adaptive_spec(name, C, loads=(int(scheme["loads"]),) * n,
                             rebalance=True)
    if scheme.get("adaptive"):
        return adaptive_spec(name, C)
    return to_spec(name, C)


def compare(results: dict, trials: int, ref: dict, ref_trials: int
            ) -> tuple:
    """(signed z of every (scheme, round), largest spread deviation) of
    one call's ``{name: (means, stderrs)}`` (per round) over ``trials``
    against the reference's over ``ref_trials``."""
    z, dev = [], 0.0
    for name, (m, se) in results.items():
        rm, rse = ref[name]
        z.append((m - rm) / np.hypot(se, rse))
        dev = max(dev, float(np.max(np.abs(
            se * math.sqrt(trials) / (rse * math.sqrt(ref_trials)) - 1.0))))
    return np.concatenate(z), dev


def reference(traffic: dict, config: dict, trials: int, seed: int,
              **kw) -> dict:
    """The plain reference's ``{name: (means, stderrs)}`` of a cell."""
    from bench.refs.adaptive_round import round_means
    return round_means(
        traffic["schemes"], config["delays"], config["process"],
        int(config["n"]), traffic["k"], traffic["rounds"], trials, seed,
        beta=traffic["feedback_beta"], gamma=traffic["coverage_gamma"],
        censored=traffic["censored_feedback"],
        block=math.gcd(trials, 16384), **kw)


class RoundsCell:
    def __init__(self, run):
        from repro.core import sweep_rounds
        self._sweep_rounds = sweep_rounds
        self.run = run
        self.n = int(run.config["n"])
        tr = run.traffic
        self.trials, self.chunk = tr["trials"], tr["chunk"]
        self.process = program_process(run.config)
        self.specs = [program_spec(s, self.n) for s in tr["schemes"]]
        self.results = {}
        self._call(derive(run.seed, "warm-up"))          # compiles

    def _call(self, seed: int) -> dict:
        import jax
        tr = self.run.traffic
        with jax.profiler.TraceAnnotation("bench.rounds_call"):
            res = self._sweep_rounds(
                self.specs, self.process, self.n, rounds=tr["rounds"],
                k=tr["k"], trials=self.trials, chunk=self.chunk, seed=seed,
                feedback_beta=tr["feedback_beta"],
                coverage_gamma=tr["coverage_gamma"],
                censored_feedback=tr["censored_feedback"])
        return {sp.name: (np.asarray(res.per_round[sp.name], np.float64),
                          np.asarray(res.stderr[sp.name], np.float64))
                for sp in self.specs}

    def call(self, i: int) -> float:
        self.results[i] = self._call(derive(self.run.seed, "call", i))
        return float(self.trials * len(self.specs)
                     * self.run.traffic["rounds"])

    def end_to_end(self, run) -> dict:
        return {"mc_evals_per_s": run.work / run.window_s}

    def check(self, run) -> dict:
        chk = run.traffic["check"]
        calls = [self.results[i] for i in sorted(self.results)]
        z, dev = np.zeros(1), 0.0
        if calls:
            ref = reference(run.traffic, run.config, chk["ref_trials"],
                            derive(run.seed, "reference"))
            z, dev = compare(pool(calls), self.trials * len(calls), ref,
                             chk["ref_trials"])
        return limited(z, dev, chk["limits"])


def pool(calls: list) -> dict:
    """``{name: (means, stderrs)}`` of calls of equal trials, pooled."""
    return {name: (np.mean([c[name][0] for c in calls], axis=0),
                   np.sqrt(np.sum([np.square(c[name][1]) for c in calls],
                                  axis=0)) / len(calls))
            for name in calls[0]}


def limited(z: np.ndarray, dev: float, limits: dict) -> dict:
    """The check's numbers beside their limits: ``z_max``, ``z_mean``
    (|mean signed z|: a bias shared by every scheme and round moves it
    where no single comparison stands out) and ``se_ratio_dev``."""
    got = {"z_max": float(np.max(np.abs(z))),
           "z_mean": float(abs(np.mean(z))), "se_ratio_dev": dev}
    return {k: {"value": v, "limit": limits[k]} for k, v in got.items()}


def setup(run) -> RoundsCell:
    return RoundsCell(run)
