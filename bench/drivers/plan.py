"""Driver: operating-point decisions through ``repro.core.plan``.

Traffic keys: ``k`` (the computation target), ``grid`` (``families``,
``loads``, ``messages``, ``comm_eps``, ``trials`` of the final rung),
``grid_seeds``, and ``check`` (the reference's ``ref_trials``, the
``limits``).  The configuration gives ``n`` and the
truncated-Gaussian ``delays``.

One call is one ``plan`` of the grid under one of ``grid_seeds`` (which
draw the race's delays and the RA matrix); its work is one decision.
Which points survive each rung depends on the grid seed, and with them
the shapes the planner compiles.  So the set-up decides once under every
grid seed, which compiles every shape, and the window takes the same
grid seeds in an order drawn from the run's seed.  After the window,
the decision of every grid seed the window reached is compared with the
plain Monte-Carlo of ``bench/refs/round_mc.py`` over every raceable point
of the grid, on independent draws.  With z the planner's predicted mean
of its winner less the reference's mean of that point, in combined
standard errors:

- ``pred_z_max``: the largest |z|;
- ``pred_z_mean``: |the mean of z| over the grid seeds, which a small
  bias shared by every decision moves;
- ``regret_z``: how far the reference puts a winner above the reference's
  own best point, in combined standard errors (0 when the winner is the
  reference's best).
"""
from __future__ import annotations

import math

import numpy as np

from bench.drivers.sweep import delay_model
from bench.seeds import derive


def point_name(fam: str, r: int, m, eps: float) -> str:
    """The planner's name of an all-k grid point."""
    parts = [fam, f"r{r}"]
    if m is not None:
        parts.append(f"m{m}")
    if eps:
        parts.append(f"eps{eps:g}")
    return "/".join(parts)


def raceable_points(grid: dict, n: int, seed: int) -> list:
    """Every schedulable point of the grid as a reference scheme: the
    oracle bound ``lb`` is not schedulable; ra needs r = n; a message
    budget may not exceed the load; pc sends one message and pc / pcmm
    carry no per-message overhead; pcmm needs n*r >= 2n - 1 partials."""
    out = []
    for r in grid["loads"]:
        for fam in grid["families"]:
            for m in grid["messages"]:
                for eps in grid["comm_eps"]:
                    if fam == "lb" or (m is not None and m > r):
                        continue
                    if fam == "ra" and r != n:
                        continue
                    if fam == "pc" and (eps or m not in (None, 1)):
                        continue
                    if fam == "pcmm" and (eps or n * r < 2 * n - 1):
                        continue
                    out.append({"name": point_name(fam, r, m, eps),
                                "family": fam, "r": r, "messages": m,
                                "comm_eps": eps, "seed": seed})
    return out


def compare(decision: dict, ref: dict) -> tuple:
    """(signed z, regret_z) of one decision against the reference's
    ``{point: (mean, se)}`` of its grid."""
    w = decision["winner"]
    rm, rse = ref[w]
    z = (decision["mean"] - rm) / math.hypot(decision["se"], rse)
    best = min(ref, key=lambda p: ref[p][0])
    bm, bse = ref[best]
    regret_z = 0.0 if best == w else (rm - bm) / math.hypot(rse, bse)
    return z, max(regret_z, 0.0)


def reference(grid: dict, n: int, k: int, delays: dict, grid_seeds,
              trials: int, seed: int) -> dict:
    """``{grid seed: {point: (mean, se)}}`` from one reference run: the
    points that do not depend on the grid seed once, ra under each."""
    from bench.refs.round_mc import round_means
    fixed = [p for p in raceable_points(grid, n, 0) if p["family"] != "ra"]
    ra = [dict(p, name=f"{p['name']}@{g}")
          for g in grid_seeds for p in raceable_points(grid, n, g)
          if p["family"] == "ra"]
    got = round_means(fixed + ra, delays, n, k, trials, seed)
    out = {}
    for g in grid_seeds:
        out[g] = {p["name"]: got[p["name"]] for p in fixed}
        out[g].update({p["name"][:-len(f"@{g}")]: got[p["name"]]
                       for p in ra if p["name"].endswith(f"@{g}")})
    return out


def check_decisions(decisions, grid, n, k, delays, chk, seed) -> dict:
    """The compared numbers of the decisions ``{grid seed: decision}``."""
    ref = reference(grid, n, k, delays, sorted(decisions), chk["ref_trials"],
                    seed)
    zs, rz = [], 0.0
    for g, d in decisions.items():
        z, r = compare(d, ref[g])
        zs.append(z)
        rz = max(rz, r)
    lim = chk["limits"]
    values = {"pred_z_max": max((abs(z) for z in zs), default=0.0),
              "pred_z_mean": abs(sum(zs) / len(zs)) if zs else 0.0,
              "regret_z": rz}
    return {name: {"value": v, "limit": lim[name]}
            for name, v in values.items()}


class PlanCell:
    def __init__(self, run):
        from repro.core import plan
        self._plan = plan
        self.run = run
        self.n = int(run.config["n"])
        self.k = int(run.traffic["k"])
        self.model = delay_model(run.config["delays"])
        self.decisions = {}
        seeds = run.traffic["grid_seeds"]
        for g in seeds:
            self._call(g)
        self.order = np.random.default_rng(
            derive(run.seed, "order")).permutation(seeds)

    def grid(self, seed: int):
        from repro.core import GridSpec
        g = self.run.traffic["grid"]
        return GridSpec(n=self.n, families=tuple(g["families"]),
                        loads=tuple(g["loads"]),
                        messages=tuple(g["messages"]),
                        comm_eps=tuple(g["comm_eps"]), trials=g["trials"],
                        seed=seed)

    def _call(self, seed: int) -> dict:
        import jax
        with jax.profiler.TraceAnnotation("bench.plan_call"):
            res = self._plan(self.grid(seed), self.model, k=self.k)
        return {"seed": seed, "winner": res.winner,
                "mean": res.predicted_mean, "se": res.predicted_stderr,
                "trials_spent": res.trials_spent,
                "lb_trials": res.trials_spent - sum(
                    rec["trials"] for rec in res.points.values()),
                "point_trials": {p: rec["trials"]
                                 for p, rec in res.points.items()}}

    def call(self, i: int) -> float:
        d = self._call(int(self.order[i % len(self.order)]))
        self.decisions[i] = d
        self.run.extra.setdefault("decisions", []).append(d)
        return 1.0

    def end_to_end(self, run) -> dict:
        return {"plan_s": run.window_s / run.work}

    def check(self, run) -> dict:
        by_seed = {d["seed"]: d for d in self.decisions.values()}
        return check_decisions(by_seed, run.traffic["grid"], self.n, self.k,
                               run.config["delays"], run.traffic["check"],
                               derive(run.seed, "reference"))


def setup(run) -> PlanCell:
    return PlanCell(run)
