"""Driver: repeated single-round sweeps through ``repro.core.sweep``.

Traffic keys: ``k`` (the round's target), ``trials`` and ``chunk`` of one
call, ``schemes`` (``{"name", "family", "r"}``, ``"seed"`` for ``ra``, and
optionally ``"messages"`` and ``"comm_eps"``), and
``check`` (``calls`` compared, the reference's ``ref_trials``, and the
``limits``).  The configuration gives ``n`` and the truncated-Gaussian
``delays`` (eq. 66).

One call is one ``sweep`` of every scheme over ``trials`` fresh trials
from a seed of its own; its work is trials x schemes evaluations.  After
the window, ``check.calls`` calls drawn from the run's seed are compared
scheme by scheme with the plain Monte-Carlo of ``bench/refs/round_mc.py``
on independent draws:

- ``z_max``: the largest |engine mean - reference mean| in combined
  standard errors;
- ``se_ratio_dev``: the largest |engine spread / reference spread - 1|,
  each spread being a standard error times the square root of its
  trials: it catches a call that averaged fewer trials than it reports.
"""
from __future__ import annotations

import math

import numpy as np

from bench.seeds import derive


def program_spec(scheme: dict, n: int):
    """The program's ``SchemeSpec`` of one traffic scheme."""
    from repro.core import (cyclic_to_matrix, lb_spec, pc_spec, pcmm_spec,
                            random_assignment_to_matrix, staircase_to_matrix,
                            to_spec)
    fam, name, r = scheme["family"], scheme["name"], int(scheme["r"])
    m, eps = scheme.get("messages"), scheme.get("comm_eps", 0.0)
    if fam == "cs":
        return to_spec(name, cyclic_to_matrix(n, r), m, comm_eps=eps)
    if fam == "ss":
        return to_spec(name, staircase_to_matrix(n, r), m, comm_eps=eps)
    if fam == "ra":
        return to_spec(name, random_assignment_to_matrix(
            n, seed=int(scheme.get("seed", 0))), m, comm_eps=eps)
    if fam == "lb":
        return lb_spec(r, name=name, messages=m, comm_eps=eps)
    if fam == "pc":
        return pc_spec(r, name=name)
    if fam == "pcmm":
        return pcmm_spec(r, name=name, messages=m)
    raise ValueError(f"unknown family {fam!r}")


def delay_model(delays: dict):
    from repro.core import TruncatedGaussianDelays
    return TruncatedGaussianDelays(
        mu1=delays["mu1"], sigma1=delays["sigma1"], a1=delays["a1"],
        mu2=delays["mu2"], sigma2=delays["sigma2"], a2=delays["a2"])


def compare(results: dict, trials: int, ref: dict, ref_trials: int
            ) -> tuple:
    """(z_max, se_ratio_dev) of one call's ``{name: (mean, se)}`` over
    ``trials`` against the reference's over ``ref_trials``."""
    z = dev = 0.0
    for name, (m, se) in results.items():
        rm, rse = ref[name]
        z = max(z, abs(m - rm) / math.hypot(se, rse))
        dev = max(dev, abs(se * math.sqrt(trials)
                           / (rse * math.sqrt(ref_trials)) - 1.0))
    return z, dev


class SweepCell:
    def __init__(self, run):
        from repro.core import sweep
        self._sweep = sweep
        self.run = run
        self.n = int(run.config["n"])
        tr = run.traffic
        self.k, self.trials, self.chunk = tr["k"], tr["trials"], tr["chunk"]
        self.model = delay_model(run.config["delays"])
        self.specs = [program_spec(s, self.n) for s in tr["schemes"]]
        self.results = {}
        self._call(derive(run.seed, "warm-up"))          # compiles

    def _call(self, seed: int) -> dict:
        import jax
        with jax.profiler.TraceAnnotation("bench.sweep_call"):
            res = self._sweep(self.specs, self.model, self.n,
                              trials=self.trials, chunk=self.chunk,
                              ks=self.k, seed=seed)
        return {sp.name: (res.at_k(sp.name, self.k),
                          float(np.ravel(res.stderr[sp.name])[-1]))
                for sp in self.specs}

    def call(self, i: int) -> float:
        self.results[i] = self._call(derive(self.run.seed, "call", i))
        return float(self.trials * len(self.specs))

    def end_to_end(self, run) -> dict:
        return {"mc_evals_per_s": run.work / run.window_s}

    def check(self, run) -> dict:
        from bench.refs.round_mc import round_means
        chk = run.traffic["check"]
        done = sorted(self.results)
        rng = np.random.default_rng(derive(run.seed, "check"))
        picked = rng.choice(done, size=min(chk["calls"], len(done)),
                            replace=False) if done else []
        z = dev = 0.0
        for i in sorted(int(p) for p in picked):
            ref = round_means(run.traffic["schemes"], run.config["delays"],
                              self.n, self.k, chk["ref_trials"],
                              derive(run.seed, "reference", i))
            zi, di = compare(self.results[i], self.trials, ref,
                             chk["ref_trials"])
            z, dev = max(z, zi), max(dev, di)
        lim = chk["limits"]
        return {"z_max": {"value": z, "limit": lim["z_max"]},
                "se_ratio_dev": {"value": dev, "limit": lim["se_ratio_dev"]}}


def setup(run) -> SweepCell:
    return SweepCell(run)
