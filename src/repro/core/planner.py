"""Racing planner: successive-halving operating-point search over a
``GridSpec`` (the ROADMAP's cluster planner).

The paper's central question — which (scheme family, load ``r``, message
budget, overhead, computation target ``k``) minimizes the average round
completion time (eq. 5) — is answered exhaustively by ``stream_grid``:
every feasible cell at the full trial count.  Most cells are obviously
dominated after a few hundred trials; the planner spends Monte-Carlo
trials only where the decision is actually close, through three layers:

1. **Theory pruning** (zero trials).  When the delay model's marginals
   have a closed form (``theory.delay_model_pdfs``), each operating
   point's oracle lower bound (eq. 46, ``theory.operating_point_mean_lb``)
   is compared against the best closed-form *achievable* mean (the coded
   schemes' eqs. 51-52/56-57 expectations): a point whose lower bound
   exceeds that anchor by the slack factor cannot win and is eliminated
   before any sampling.

2. **CRN paired-difference racing**.  All surviving points are evaluated
   in ONE fused :class:`~repro.core.montecarlo.ResumableSweep` — every
   scheme sees identical delay draws (common random numbers), so two
   points are compared by their *paired per-trial differences*, whose
   stderr is far below the independent-comparison stderr whenever the
   completion times are positively correlated (they share the draws).  A
   point is eliminated when the lower confidence bound of its paired gap
   to the incumbent (the current argmin) clears zero at ``z`` sigmas.

3. **Geometric rung ladder with resumable extension**.  Trials grow by
   ``eta`` per rung; survivors are *extended* — the resumable sweep
   reuses every chunk partial already computed, so a cell raced to the
   final rung costs exactly the trials of a fresh full run, and an
   eliminated cell costs only the rungs it survived.  Survivors of the
   final rung carry the full ``GridSpec.trials``, so the returned argmin
   has the *same* confidence as the exhaustive grid's (matched
   confidence), at a fraction of the trial-evaluations.

The result is a versioned :class:`PlanResult` artifact: the recommended
:class:`~repro.core.spec.RoundConfig` (feed it to ``repro.launch.train
--config`` or the live master), the predicted-vs-lower-bound gap, the
trials spent vs. the exhaustive equivalent, and the full elimination
trajectory.  CLI: ``python -m repro.launch.plan``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
from typing import Dict, Optional

import numpy as np

from . import montecarlo as mc
from . import theory
from .. import obs
from .grid import GridSpec, _cell_name, _family_spec
from .spec import RoundConfig, _internal

__all__ = ["plan", "PlanResult", "PLAN_FORMAT_VERSION"]

PLAN_FORMAT_VERSION = 1

#: families the planner can emit a ``RoundConfig`` for (the TO-matrix
#: schedules a live round actually runs; coded winners are reported but
#: have no TO-matrix round config).
_CONFIG_FAMILIES = ("cs", "ss", "ra")


@dataclasses.dataclass(frozen=True)
class _Point:
    """One operating point: a scheme spec plus a computation target.
    Points sharing a spec (several ``k`` targets) race on the same
    evaluation columns."""
    name: str                 # grid cell name (the exhaustive grid's key)
    spec_name: str            # racing spec it reads
    family: str
    r: int
    messages: Optional[int]
    comm_eps: float
    k: int                    # effective target (coded: decode threshold)
    coded: bool               # pc/pcmm: metric is their single column


@dataclasses.dataclass
class PlanResult:
    """Outcome of one planner run.

    ``points[name]`` records each operating point's fate: ``status``
    (``won`` / ``survived`` / ``eliminated`` / ``pruned`` / ``excluded``),
    the trials it consumed, its mean/stderr at that count, the rung it
    left the race (eliminations), its paired gap to the incumbent at that
    rung, and the theory guides when available.  ``trajectory`` is the
    per-rung history (trial count, survivors, eliminations).
    ``config`` is the recommended ``RoundConfig`` when the winner is a
    TO-matrix family (cs/ss/ra), else None with ``config_note`` saying
    why.  ``trials_spent`` counts every Monte-Carlo trial-evaluation the
    planner consumed (racing + the final lower-bound run);
    ``exhaustive_trials`` is what ``stream_grid`` would have spent on the
    same grid (#cells x trials)."""
    winner: str
    predicted_mean: float
    predicted_stderr: float
    config: Optional[RoundConfig]
    config_note: Optional[str]
    points: Dict[str, dict]
    trajectory: list
    trials_spent: int
    exhaustive_trials: int
    lb_mean: Optional[float]
    lb_gap: Optional[float]
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def savings(self) -> float:
        """Exhaustive-equivalent trials per trial actually spent."""
        return (self.exhaustive_trials / self.trials_spent
                if self.trials_spent else float("inf"))

    def to_json(self) -> dict:
        from .grid import _jsonable
        return {
            "version": PLAN_FORMAT_VERSION, "kind": "plan-result",
            "winner": self.winner,
            "predicted_mean": self.predicted_mean,
            "predicted_stderr": self.predicted_stderr,
            "config": (None if self.config is None
                       else self.config.to_dict()),
            "config_note": self.config_note,
            "points": _jsonable(self.points),
            "trajectory": _jsonable(self.trajectory),
            "trials_spent": self.trials_spent,
            "exhaustive_trials": self.exhaustive_trials,
            "lb_mean": self.lb_mean, "lb_gap": self.lb_gap,
            "meta": _jsonable(self.meta),
        }

    def save(self, path: str) -> str:
        with open(path, "w") as fh:
            json.dump(self.to_json(), fh)
        return path

    @classmethod
    def load(cls, path: str) -> "PlanResult":
        with open(path) as fh:
            doc = json.load(fh)
        if doc.get("kind") != "plan-result":
            raise ValueError(f"{path}: not a plan-result artifact "
                             f"(kind={doc.get('kind')!r})")
        v = doc.get("version", 0)
        if v > PLAN_FORMAT_VERSION:
            raise ValueError(f"{path}: plan-result version {v} is newer "
                             f"than this reader ({PLAN_FORMAT_VERSION})")
        cfg = doc.get("config")
        return cls(
            winner=doc["winner"], predicted_mean=doc["predicted_mean"],
            predicted_stderr=doc["predicted_stderr"],
            config=None if cfg is None else RoundConfig.from_dict(cfg),
            config_note=doc.get("config_note"),
            points=doc["points"], trajectory=doc["trajectory"],
            trials_spent=doc["trials_spent"],
            exhaustive_trials=doc["exhaustive_trials"],
            lb_mean=doc.get("lb_mean"), lb_gap=doc.get("lb_gap"),
            meta=doc.get("meta", {}))


def _enumerate_points(gs: GridSpec, k_default: int):
    """The grid's operating points and the deduplicated racing specs.

    Points differing only in the target ``k`` share one spec (same draws,
    same evaluation — ``k`` is just a column of the all-k statistic), so
    the racing sweep carries each (family, r, messages, eps) spec once.
    ``lb`` cells are excluded from the race — the oracle bound dominates
    every schedule at its own load by construction, so racing it would
    always "win" with an unrealizable operating point; it returns as the
    final predicted-vs-LB gap instead."""
    specs: Dict[str, mc.SchemeSpec] = {}
    points: list[_Point] = []
    excluded: list[str] = []
    for r in gs.loads:
        for fam in gs.families:
            for m in gs.messages:
                for eps in gs.comm_eps:
                    sp = _family_spec(fam, gs.n, r, m, eps, gs.seed)
                    if sp is None:
                        continue
                    sname = _cell_name(fam, r, m, eps, None)
                    for k in gs.ks:
                        cname = _cell_name(fam, r, m, eps, k)
                        if fam == "lb":
                            excluded.append(cname)
                            continue
                        coded = fam in ("pc", "pcmm")
                        if coded:
                            k_eff = (mc._pc_threshold(gs.n, r) if fam == "pc"
                                     else mc._pcmm_threshold(gs.n))
                        else:
                            k_eff = k if k is not None else k_default
                        if sname not in specs:
                            with _internal():
                                specs[sname] = dataclasses.replace(
                                    sp, name=sname)
                        points.append(_Point(
                            name=cname, spec_name=sname, family=fam, r=r,
                            messages=m, comm_eps=eps, k=int(k_eff),
                            coded=coded))
    if not points:
        raise ValueError("grid has no raceable operating points (only lb "
                         "cells?); nothing to plan")
    names = [p.name for p in points]
    if len(set(names)) != len(names):       # duplicate (fam,r,m,eps,k)
        raise ValueError(f"duplicate operating points in grid: "
                         f"{sorted(nm for nm in set(names) if names.count(nm) > 1)}")
    return specs, points, excluded


def _theory_prune(points, pdfs, n: int, slack: float):
    """Split points into (pruned names -> guide record, kept points).

    Anchor: the smallest closed-form *achievable* mean among the grid's
    coded points (eqs. 51-52 / 56-57).  A point whose oracle-lower-bound
    guide exceeds ``(1 + slack) * anchor`` cannot be the argmin.  Both
    sides assume FIFO in-order delivery within a worker (see
    ``theory.multimessage_coded_tail``) — the slack absorbs that
    approximation, so pruning stays conservative."""
    pdf1, pdf2, sup1, sup2 = pdfs

    def _tmax(p: _Point) -> float:
        m_eff = p.r if p.messages is None else min(p.messages, p.r)
        return 1.25 * (p.r * sup1 + sup2 + m_eff * p.comm_eps)

    anchor = None
    predicted: Dict[str, float] = {}
    for p in points:
        if not p.coded:
            continue
        if p.family == "pc":
            mu = theory.multimessage_coded_mean(
                n, p.r, 1, pdf1, pdf2, tmax=_tmax(p),
                threshold=mc._pc_threshold(n, p.r))
        else:
            m_eff = p.r if p.messages is None else min(p.messages, p.r)
            mu = theory.multimessage_coded_mean(
                n, p.r, m_eff, pdf1, pdf2, tmax=_tmax(p))
        predicted[p.name] = mu
        anchor = mu if anchor is None else min(anchor, mu)
    if anchor is None:          # no closed-form achievable mean to prune on
        return {}, list(points), predicted
    pruned: Dict[str, dict] = {}
    kept = []
    for p in points:
        guide = theory.operating_point_mean_lb(
            n, p.r, p.k, pdf1, pdf2, messages=p.messages,
            comm_eps=p.comm_eps, tmax=_tmax(p))
        if guide > (1.0 + slack) * anchor:
            pruned[p.name] = {"lb_guide": guide, "anchor": anchor}
        else:
            kept.append(p)
    if not kept:                # slack misconfigured — never prune everything
        return {}, list(points), predicted
    return pruned, kept, predicted


def _rung_ladder(trials: int, base: int, eta: int) -> list[int]:
    """Geometric rung totals ``base * eta^j`` capped at ``trials`` (the
    final rung always lands exactly on ``trials``)."""
    ladder, t = [], base
    while t < trials:
        ladder.append(t)
        t *= eta
    ladder.append(trials)
    return ladder


def _metric_column(samp: np.ndarray, p: _Point, n: int) -> np.ndarray:
    """Per-trial completion times of one operating point, float64.
    All-k sweeps give TO/lb specs one column per k; coded specs carry
    their own decode threshold in a single column."""
    x = np.asarray(samp, np.float64)
    if x.shape[1] == 1:
        return x[:, 0]
    return x[:, p.k - 1]


@obs.span("plan.decide")
def plan(grid: GridSpec, model, *, k: Optional[int] = None,
         base_trials: Optional[int] = None, eta: int = 4, z: float = 3.0,
         theory_prune: bool = True, prune_slack: float = 0.25,
         devices=None) -> PlanResult:
    """Find the grid's argmin operating point by successive-halving racing
    (see the module docstring) instead of exhaustive streaming.

    Parameters
    ----------
    grid:   the ``GridSpec`` to search (same declarative object
            ``stream_grid`` consumes; ``grid.trials`` is the final rung's
            — and the exhaustive sweep's — trial count).
    model:  the delay model.
    k:      computation target for all-k cells (``grid.ks`` entries that
            are ``None``); defaults to ``n``.  Cells with an explicit
            ``ks`` race at their own target.
    base_trials: first-rung trial count (default ``grid.trials / eta^3``,
            at least 256).  Also the racing chunk size when ``grid.chunk``
            is unset, so every intermediate rung stays chunk-aligned for
            the resumable extension.
    eta:    rung growth factor (>= 2).
    z:      elimination threshold in paired-gap sigmas.  Also used for
            the survivor tie report.
    theory_prune: eliminate points whose closed-form oracle lower bound
            exceeds the best closed-form achievable mean before any MC
            (only when ``theory.delay_model_pdfs(model)`` knows the
            model's marginals, and only with coded cells in the grid to
            anchor on).
    prune_slack: safety factor on the pruning comparison (the closed
            forms assume FIFO message delivery; see
            ``theory.operating_point_mean_lb``).
    devices: shard the racing sweep's trial axis (as in ``sweep``).

    The race runs in all-k mode — one sort per trial serves every target —
    and compares points by paired per-trial differences under common
    random numbers, eliminating at ``z`` sigmas against the incumbent.
    Survivors of the final rung reach ``grid.trials`` exactly, so the
    argmin confidence matches the exhaustive grid's.
    """
    t0 = time.perf_counter()
    n = grid.n
    k_default = n if k is None else int(k)
    if not 1 <= k_default <= n:
        raise ValueError(f"need 1 <= k <= n={n}, got k={k_default}")
    if eta < 2:
        raise ValueError(f"eta must be >= 2, got {eta}")
    if z <= 0:
        raise ValueError(f"z must be > 0, got {z}")

    specs, points, excluded = _enumerate_points(grid, k_default)
    exhaustive_cells = len(points) + len(excluded)
    exhaustive_trials = exhaustive_cells * grid.trials

    records: Dict[str, dict] = {}
    for cname in excluded:
        records[cname] = {"status": "excluded", "trials": 0,
                          "note": "lb is the oracle bound, not a "
                                  "schedulable operating point; it returns "
                                  "as the final predicted-vs-LB gap"}

    # ---- layer 1: closed-form dominance pruning (zero trials) -----------
    predicted: Dict[str, float] = {}
    pdfs = theory.delay_model_pdfs(model) if theory_prune else None
    if pdfs is not None:
        with obs.span("plan.prune"):
            pruned, points, predicted = _theory_prune(points, pdfs, n,
                                                      prune_slack)
        for cname, rec in pruned.items():
            records[cname] = {"status": "pruned", "trials": 0, **rec}

    # ---- rung ladder ----------------------------------------------------
    if base_trials is None:
        base_trials = max(256, -(-grid.trials // eta ** 3))
    base_trials = int(min(base_trials, grid.trials))
    chunk = grid.chunk if grid.chunk is not None else base_trials
    chunk = int(min(chunk, base_trials))
    if base_trials % chunk:
        raise ValueError(
            f"base_trials ({base_trials}) must be a multiple of the grid "
            f"chunk ({chunk}) so every rung total stays chunk-aligned for "
            f"the resumable extension")
    ladder = _rung_ladder(grid.trials, base_trials, eta)

    # ---- layer 2+3: CRN-paired successive-halving race ------------------
    alive = list(points)
    needed = {p.spec_name for p in alive}
    rs = mc.resumable_sweep(
        [sp for nm, sp in specs.items() if nm in needed], model, n,
        seed=grid.seed, chunk=chunk, ks=None, devices=devices,
        keep_samples=True)
    trajectory: list[dict] = []
    spec_trials: Dict[str, int] = {}

    for rung, t in enumerate(ladder):
        with obs.span("plan.rung", rung=rung, trials=t):
            rs.extend_trials(t)
            with obs.span("plan.race"):
                samp = rs.samples()
                cols = {p.name: _metric_column(samp[p.spec_name], p, n)
                        for p in alive}
                means = {nm: float(x.mean()) for nm, x in cols.items()}
                # the incumbent: this rung's argmin
                inc = min(alive, key=lambda p: means[p.name])
                x_inc = cols[inc.name]
                eliminated: list[dict] = []
                survivors: list[_Point] = []
                for p in alive:
                    if p is inc:
                        survivors.append(p)
                        continue
                    d = cols[p.name] - x_inc        # paired gap, CRN
                    gap = float(d.mean())
                    gap_se = (float(d.std(ddof=1) / math.sqrt(t)) if t > 1
                              else 0.0)
                    if rung < len(ladder) - 1 and gap - z * gap_se > 0.0:
                        x = cols[p.name]
                        records[p.name] = {
                            "status": "eliminated", "trials": t,
                            "rung": rung,
                            "mean": means[p.name],
                            "stderr": float(x.std(ddof=1) / math.sqrt(t)),
                            "gap": gap, "gap_stderr": gap_se,
                            "vs": inc.name,
                        }
                        eliminated.append({"point": p.name, "gap": gap,
                                           "gap_stderr": gap_se})
                    else:
                        survivors.append(p)
                trajectory.append({
                    "rung": rung, "trials": t, "incumbent": inc.name,
                    "survivors": [p.name for p in survivors],
                    "eliminated": [e["point"] for e in eliminated],
                })
                dropped_specs = ({p.spec_name for p in alive}
                                 - {p.spec_name for p in survivors})
                for snm in dropped_specs:
                    spec_trials[snm] = t
                alive = survivors
                if rung < len(ladder) - 1 and dropped_specs:
                    rs.narrow([p.spec_name for p in alive])
    for snm in {p.spec_name for p in alive}:
        spec_trials[snm] = grid.trials

    # ---- final selection + survivor records -----------------------------
    with obs.span("plan.select"):
        samp = rs.samples()
        final_cols = {p.name: _metric_column(samp[p.spec_name], p, n)
                      for p in alive}
        winner = min(alive, key=lambda p: float(final_cols[p.name].mean()))
        w_x = final_cols[winner.name]
        w_mean = float(w_x.mean())
        w_se = float(w_x.std(ddof=1) / math.sqrt(grid.trials))
        for p in alive:
            x = final_cols[p.name]
            rec = {"status": "won" if p is winner else "survived",
                   "trials": grid.trials, "mean": float(x.mean()),
                   "stderr": float(x.std(ddof=1) / math.sqrt(grid.trials))}
            if p is not winner:
                d = x - w_x
                rec["gap"] = float(d.mean())
                rec["gap_stderr"] = float(d.std(ddof=1)
                                          / math.sqrt(grid.trials))
                rec["vs"] = winner.name
            records[p.name] = rec
        for nm, mu in predicted.items():
            if nm in records:
                records[nm]["theory_mean"] = mu

    # ---- predicted-vs-LB gap at the winning operating point -------------
    trials_spent = sum(spec_trials.values())
    lb_sp = mc.lb_spec(winner.r, messages=winner.messages,
                       comm_eps=winner.comm_eps)
    with obs.span("plan.lb_sweep"):
        lb_res = mc.sweep([lb_sp], model, n, trials=grid.trials,
                          seed=grid.seed, chunk=chunk, ks=None,
                          devices=devices)
    # coded winners recover the full gradient at their decode threshold,
    # so the comparable oracle target is k = n (their own threshold can
    # exceed n and is not an order-statistic index of the lb spec).
    lb_mean = lb_res.at_k("lb", n if winner.coded else winner.k)
    lb_gap = (w_mean - lb_mean) / lb_mean if lb_mean > 0 else float("inf")
    trials_spent += grid.trials

    # ---- RoundConfig emission -------------------------------------------
    config = config_note = None
    if winner.family in _CONFIG_FAMILIES:
        config = RoundConfig(
            n=n, k=winner.k, kind=winner.family, r=winner.r,
            messages=winner.messages, comm_eps=winner.comm_eps,
            seed=grid.seed)
    else:
        config_note = (f"winner {winner.name!r} is a coded scheme "
                       f"({winner.family}); it has no TO-matrix round "
                       f"config — wire its encoder in directly")

    ties = [p.name for p in alive if p is not winner
            and records[p.name]["gap"]
            <= z * records[p.name]["gap_stderr"]]
    meta = {
        "n": n, "k": k_default, "eta": eta, "z": z,
        "base_trials": base_trials, "chunk": chunk, "ladder": ladder,
        "theory_pruned": sum(1 for r2 in records.values()
                             if r2["status"] == "pruned"),
        "raced_points": len(points), "excluded": len(excluded),
        "exhaustive_cells": exhaustive_cells,
        "ties": ties,
        "seconds": time.perf_counter() - t0,
        "devices": (devices if isinstance(devices, (int, type(None)))
                    else len(tuple(devices))),
    }
    return PlanResult(
        winner=winner.name, predicted_mean=w_mean, predicted_stderr=w_se,
        config=config, config_note=config_note, points=records,
        trajectory=trajectory, trials_spent=trials_spent,
        exhaustive_trials=exhaustive_trials, lb_mean=lb_mean,
        lb_gap=lb_gap, meta=meta)
