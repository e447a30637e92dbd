"""Streaming grid-sweep engine: full (scheme family × load × message budget
× comm_eps × k) grids at 10^8-trial scale without per-cell recompilation or
dispatch stalls.

The paper's central object is the average completion time as a *function*
of computation load and computation target, but evaluating every point of
that surface as its own ``sweep`` call leaves two kinds of time on the
table:

1. **Recompiles.**  ``stream_grid`` rides the shape-bucketed executor
   cache (``montecarlo._eval_layout``): every cell whose scheme-kind
   structure lands in the same ``(n, r_max, ks, counts)`` bucket reuses
   one compiled program with its own runtime gather plans — at most one
   compile per shape bucket for the whole grid.

2. **Dispatch stalls.**  Cells that share their draw-defining coordinates
   ``(n, r_max, ks, trials, seed, chunk, model)`` are *fused* into one
   multi-spec sweep (bit-exact with the per-cell path under common random
   numbers: same ``fold_in`` trial keys, same ``(n, r_max)`` delay draws,
   independent per-spec evaluation, same global-chunk-order float64 host
   combine) — amortizing the dominant cost, delay sampling, across every
   scheme at that load.  Groups that cannot fuse are *pipelined*: group
   ``j+1`` is dispatched while group ``j``'s per-chunk float32 partials
   are still in flight (JAX async dispatch; a small double-buffered
   window), so the device never idles on the host combine.

Rounds-axis cells (``GridCell(rounds=..., k=...)``) are evaluated per cell
through ``sweep_rounds`` — the adaptive rounds scan bakes its specs into
the compiled program, so rounds cells neither fuse nor bucket; they are
supported so one grid artifact can carry both surfaces.

``stream_grid`` returns a :class:`GridResult` whose versioned JSON
artifact (``save``/``load``) is the interchange format for the planned
cluster planner (ROADMAP) and the CI grid smoke leg.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import math
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from . import montecarlo as mc
from .montecarlo import (SchemeSpec, lb_spec, pc_spec, pcmm_spec, sweep_rounds,
                         to_spec)
from .scheduling import (cyclic_to_matrix, random_assignment_to_matrix,
                         staircase_to_matrix)

__all__ = ["GridCell", "GridSpec", "GridResult", "stream_grid",
           "GRID_FORMAT_VERSION", "FAMILIES"]

GRID_FORMAT_VERSION = 1

#: scheme families ``GridSpec`` can enumerate.  ``cs``/``ss``/``ra`` are the
#: paper's TO-matrix schedules, ``lb`` the oracle bound, ``pc``/``pcmm`` the
#: coded schemes (their decode thresholds ignore the sweep ``k``).
FAMILIES = ("cs", "ss", "ra", "lb", "pc", "pcmm")


@dataclasses.dataclass(frozen=True)
class GridCell:
    """One grid point: a named spec set evaluated at fixed MC coordinates.

    Single-round cells (``rounds=None``) go through the fused/pipelined
    ``sweep`` path; rounds cells (``rounds`` + ``k`` set) through
    ``sweep_rounds`` with the usual adaptive/deadline knobs."""
    name: str
    specs: Tuple[SchemeSpec, ...]
    n: int
    model: object
    trials: int = 20000
    seed: int = 0
    chunk: Optional[int] = None
    ks: Optional[int] = None
    # rounds-axis cells:
    rounds: Optional[int] = None
    k: Optional[int] = None
    feedback_beta: float = 0.7
    coverage_gamma: float = 0.5
    censored_feedback: bool = False
    deadline: Optional[float] = None
    deadline_policy: str = "wait"

    def __post_init__(self):
        object.__setattr__(self, "specs", tuple(self.specs))
        if not self.specs:
            raise ValueError(f"cell {self.name!r}: need at least one spec")
        if (self.rounds is None) != (self.k is None):
            raise ValueError(f"cell {self.name!r}: rounds cells need both "
                             f"rounds= and k= (got rounds={self.rounds}, "
                             f"k={self.k})")

    @property
    def is_rounds(self) -> bool:
        return self.rounds is not None

    @property
    def r_max(self) -> int:
        """The cell's slot-grid width — the draw-shape the per-cell path
        samples at, so only cells with equal ``r_max`` may fuse."""
        return max(sp.load for sp in self.specs)


def _family_spec(fam: str, n: int, r: int, m: Optional[int], eps: float,
                 seed: int) -> Optional[SchemeSpec]:
    """The family's spec at one (r, messages, comm_eps) point, or None when
    the combination is infeasible for that family (skipped, not an error —
    a declarative grid naturally contains corners like pc × messages=4)."""
    if m is not None and m > r:
        return None
    if fam in ("cs", "ss", "ra"):
        if fam == "ra" and r != n:     # RA permutes full columns: r == n
            return None
        C = {"cs": cyclic_to_matrix, "ss": staircase_to_matrix,
             "ra": lambda nn, rr: random_assignment_to_matrix(
                 nn, rr, seed=seed)}[fam](n, r)
        return to_spec(fam, C, messages=m, comm_eps=eps)
    if fam == "lb":
        return lb_spec(r, messages=m, comm_eps=eps)
    if fam == "pc":
        # one-shot by construction; no per-message overhead model
        if eps or (m is not None and m != 1):
            return None
        return pc_spec(r)
    if fam == "pcmm":
        if eps or n * r < 2 * n - 1:       # no overhead model / infeasible
            return None
        return pcmm_spec(r, messages=m)
    raise ValueError(f"unknown scheme family {fam!r}; have {FAMILIES}")


def _cell_name(fam: str, r: int, m: Optional[int], eps: float,
               k: Optional[int]) -> str:
    parts = [fam, f"r{r}"]
    if m is not None:
        parts.append(f"m{m}")
    if eps:
        parts.append(f"eps{eps:g}")
    if k is not None:
        parts.append(f"k{k}")
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Declarative grid: the cross product of scheme families × loads ×
    message budgets × per-message overheads × computation targets, at
    shared MC coordinates.  Infeasible corners (pc × multi-message,
    pcmm below its decode threshold, budgets above the load) are skipped.

    ``ks`` entries are computation targets: ``None`` = all-k mode (one
    sort yields every k in 1..n), an int = that single order statistic.

    JSON round-trip (``to_json``/``from_json``) is the CLI input format of
    ``python -m repro.launch.grid``.
    """
    n: int
    families: Tuple[str, ...] = ("cs", "ss", "lb", "pc")
    loads: Tuple[int, ...] = (2,)
    messages: Tuple[Optional[int], ...] = (None,)
    comm_eps: Tuple[float, ...] = (0.0,)
    ks: Tuple[Optional[int], ...] = (None,)
    trials: int = 20000
    seed: int = 0
    chunk: Optional[int] = None

    def __post_init__(self):
        for f2 in ("families", "loads", "messages", "comm_eps", "ks"):
            object.__setattr__(self, f2, tuple(getattr(self, f2)))
        bad = [f2 for f2 in self.families if f2 not in FAMILIES]
        if bad:
            raise ValueError(f"unknown families {bad}; have {FAMILIES}")
        if not (self.families and self.loads and self.messages
                and self.comm_eps and self.ks):
            raise ValueError("every grid axis needs at least one value")

    def cells(self, model) -> Tuple[GridCell, ...]:
        """Enumerate the grid as one single-spec ``GridCell`` per feasible
        (family, r, messages, eps, k) point, all sharing ``model`` and the
        MC coordinates — maximally fusable by ``stream_grid``."""
        out = []
        for r in self.loads:
            for fam in self.families:
                for m in self.messages:
                    for eps in self.comm_eps:
                        sp = _family_spec(fam, self.n, r, m, eps, self.seed)
                        if sp is None:
                            continue
                        for k in self.ks:
                            out.append(GridCell(
                                name=_cell_name(fam, r, m, eps, k),
                                specs=(sp,), n=self.n, model=model,
                                trials=self.trials, seed=self.seed,
                                chunk=self.chunk, ks=k))
        if not out:
            raise ValueError("grid is empty: every (family, load, budget) "
                             "combination was infeasible")
        return tuple(out)

    def to_json(self) -> dict:
        return {"version": GRID_FORMAT_VERSION, "kind": "grid-spec",
                "n": self.n, "families": list(self.families),
                "loads": list(self.loads),
                "messages": list(self.messages),
                "comm_eps": list(self.comm_eps), "ks": list(self.ks),
                "trials": self.trials, "seed": self.seed,
                "chunk": self.chunk}

    @classmethod
    def from_json(cls, doc: dict) -> "GridSpec":
        if doc.get("kind", "grid-spec") != "grid-spec":
            raise ValueError(f"not a grid-spec document: "
                             f"kind={doc.get('kind')!r}")
        v = doc.get("version", GRID_FORMAT_VERSION)
        if v > GRID_FORMAT_VERSION:
            raise ValueError(f"grid-spec version {v} is newer than this "
                             f"reader ({GRID_FORMAT_VERSION})")
        kw = {k2: doc[k2] for k2 in ("n", "families", "loads", "messages",
                                     "comm_eps", "ks", "trials", "seed",
                                     "chunk") if k2 in doc}
        return cls(**kw)


# ------------------------------ result artifact ------------------------------

_ARRAY_FIELDS = ("means", "stderr", "per_round", "wallclock",
                 "wallclock_stderr")


def _jsonable(x):
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    if isinstance(x, dict):
        return {k2: _jsonable(v) for k2, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    return x


def _arrays_back(cell: dict) -> dict:
    out = dict(cell)
    for f2 in _ARRAY_FIELDS:
        if f2 in out:
            out[f2] = {k2: np.asarray(v, np.float64)
                       for k2, v in out[f2].items()}
    if out.get("degradation"):
        out["degradation"] = {
            nm: {k2: np.asarray(v, np.float64) for k2, v in d.items()}
            for nm, d in out["degradation"].items()}
    return out


@dataclasses.dataclass
class GridResult:
    """Per-cell statistics of one ``stream_grid`` run plus run metadata
    (cells/sec, shape-bucket count, fused dispatch count, devices).

    ``cells[name]`` is a plain dict: ``kind`` (``"sweep"``/``"rounds"``),
    the cell's MC coordinates, and its statistics — ``means``/``stderr``
    per scheme for sweep cells (one column per k in all-k mode), the
    ``sweep_rounds`` streams (``per_round``, ``wallclock``, stderrs, and
    ``degradation`` when a deadline was set) for rounds cells.  The JSON
    artifact is versioned and round-trips through ``save``/``load``.
    """
    cells: Dict[str, dict]
    meta: dict = dataclasses.field(default_factory=dict)

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ValueError(f"unknown grid cell {name!r}; have "
                            f"{sorted(self.cells)[:8]}...")
        return self.cells[name]

    def means(self, name: str, scheme: Optional[str] = None) -> np.ndarray:
        c = self.cell(name)
        schemes = sorted(c["means"])
        if scheme is None:
            if len(schemes) != 1:
                raise ValueError(f"cell {name!r} has schemes {schemes}; "
                                 f"pass scheme=")
            scheme = schemes[0]
        return c["means"][scheme]

    def best_cell(self, metric: str = "mean", k: Optional[int] = None,
                  exclude: Tuple[str, ...] = ("lb",),
                  z: float = 2.0) -> dict:
        """Argmin operating point of the grid at computation target ``k``
        (defaults to each cell's ``ks``, else ``n``): the (cell, scheme)
        pair with the smallest mean completion time over the sweep cells.

        ``exclude`` drops schemes by name (default: the oracle ``lb``
        bound, which would always win but is not schedulable).  Returns
        ``{"cell", "scheme", "mean", "stderr", "ties"}`` where ``ties``
        lists the runner-up (cell, scheme) pairs whose gap to the winner
        is within ``z`` combined standard errors — the resolution limit
        of the grid's trial budget.  Rounds cells are skipped (their
        metric is a stream, not a scalar)."""
        if metric != "mean":
            raise ValueError(f"unknown metric {metric!r}; only 'mean'")
        entries = []
        for nm, c in self.cells.items():
            if c.get("kind") != "sweep":
                continue
            fixed = set(c.get("fixed", ()))
            for scheme, v in c["means"].items():
                if scheme in exclude:
                    continue
                v = np.atleast_1d(np.asarray(v, np.float64))
                se = np.atleast_1d(np.asarray(c["stderr"][scheme],
                                              np.float64))
                if v.shape[-1] == 1 or scheme in fixed:
                    col = 0
                else:
                    kk = k if k is not None else (c.get("ks") or c["n"])
                    if not 1 <= kk <= v.shape[-1]:
                        raise ValueError(f"cell {nm!r} scheme {scheme!r}: "
                                         f"need 1 <= k <= {v.shape[-1]}, "
                                         f"got {kk}")
                    col = int(kk) - 1
                entries.append((nm, scheme, float(v[col]), float(se[col])))
        if not entries:
            raise ValueError("grid has no scorable sweep cells after "
                             f"excluding {exclude}")
        nm, scheme, mu, se = min(entries, key=lambda e: e[2])
        ties = [{"cell": e[0], "scheme": e[1], "mean": e[2],
                 "stderr": e[3]}
                for e in entries if e[0] != nm or e[1] != scheme
                if e[2] - mu <= z * math.hypot(se, e[3])]
        return {"cell": nm, "scheme": scheme, "mean": mu, "stderr": se,
                "ties": ties}

    @property
    def cells_per_sec(self) -> float:
        return self.meta.get("cells_per_sec", float("nan"))

    def to_json(self) -> dict:
        return {"version": GRID_FORMAT_VERSION, "kind": "grid-result",
                "meta": _jsonable(self.meta),
                "cells": {nm: _jsonable(c) for nm, c in self.cells.items()}}

    def save(self, path: str) -> str:
        with open(path, "w") as fh:
            json.dump(self.to_json(), fh)
        return path

    @classmethod
    def load(cls, path: str) -> "GridResult":
        with open(path) as fh:
            doc = json.load(fh)
        if doc.get("kind") != "grid-result":
            raise ValueError(f"{path}: not a grid-result artifact "
                             f"(kind={doc.get('kind')!r})")
        v = doc.get("version", 0)
        if v > GRID_FORMAT_VERSION:
            raise ValueError(f"{path}: grid-result version {v} is newer "
                             f"than this reader ({GRID_FORMAT_VERSION})")
        return cls(cells={nm: _arrays_back(c)
                          for nm, c in doc["cells"].items()},
                   meta=doc.get("meta", {}))


# ----------------------------- streaming driver ------------------------------

def _model_key(model):
    """Fusion-group identity of a delay model: hashable models group by
    equality (frozen dataclasses), unhashable custom models by object
    identity — never across distinct objects."""
    try:
        hash(model)
        return model
    except TypeError:
        return id(model)


def stream_grid(cells: Sequence[GridCell], *, devices=None,
                pipeline: int = 2) -> GridResult:
    """Evaluate every cell, fusing cells that share their draw-defining
    coordinates into one multi-spec sweep and keeping up to ``pipeline``
    fused dispatches in flight (double-buffered by default).

    Bit-exactness contract: every cell's ``means``/``stderr`` are
    bit-identical to a standalone per-cell ``sweep`` (or ``sweep_rounds``)
    at the same coordinates — fusion only widens the evaluator spec stack
    over the SAME ``(n, r_max)`` delay draws, and the float64 host combine
    runs in global chunk order either way.  Pinned by
    ``tests/test_grid.py`` across dense/ragged × budgets × device counts.
    """
    cells = tuple(cells)
    if not cells:
        raise ValueError("need at least one GridCell")
    names = [c.name for c in cells]
    dup = [nm for nm, cnt in collections.Counter(names).items() if cnt > 1]
    if dup:
        raise ValueError(f"duplicate grid cell names: {dup}")
    if pipeline < 1:
        raise ValueError(f"pipeline depth must be >= 1, got {pipeline}")

    t0 = time.perf_counter()
    sweep_cells = [c for c in cells if not c.is_rounds]
    rounds_cells = [c for c in cells if c.is_rounds]

    # ---- fuse sweep cells sharing their draw-defining coordinates ----
    groups: Dict[tuple, list] = {}
    for c in sweep_cells:
        key = (c.n, c.r_max, c.ks, c.trials, c.seed, c.chunk,
               _model_key(c.model))
        groups.setdefault(key, []).append(c)

    results: Dict[str, dict] = {}
    sigs = set()
    pending: collections.deque = collections.deque()

    def _resolve_one() -> None:
        grp, handle = pending.popleft()
        means, stderr = handle.resolve()
        for cell in grp:
            results[cell.name] = {
                "kind": "sweep", "n": cell.n, "trials": cell.trials,
                "seed": cell.seed, "ks": cell.ks,
                "means": {sp.name: np.atleast_1d(
                    means[f"{cell.name}:{sp.name}"]) for sp in cell.specs},
                "stderr": {sp.name: np.atleast_1d(
                    stderr[f"{cell.name}:{sp.name}"]) for sp in cell.specs},
                "fixed": [sp.name for sp in cell.specs
                          if sp.kind in ("pc", "pcmm")],
            }

    for key, grp in groups.items():
        c0 = grp[0]
        # spec names are only unique per cell — prefix with the cell name
        # (outside the compiled program: outputs are group-keyed, so the
        # renames never retrace).
        fused = [dataclasses.replace(sp, name=f"{cell.name}:{sp.name}")
                 for cell in grp for sp in cell.specs]
        sig, _, _ = mc._eval_layout(tuple(fused), c0.n, c0.r_max, c0.ks)
        sigs.add(sig)
        while len(pending) >= pipeline:       # keep the window bounded
            _resolve_one()
        pending.append((grp, mc._dispatch_run(
            fused, c0.model, c0.n, trials=c0.trials, seed=c0.seed,
            chunk=c0.chunk, ks=c0.ks, want_samples=False, devices=devices)))
    while pending:
        _resolve_one()

    # ---- rounds cells: per-cell sweep_rounds (unfused, unbucketed) ----
    for cell in rounds_cells:
        res = sweep_rounds(cell.specs, cell.model, cell.n,
                           rounds=cell.rounds, k=cell.k, trials=cell.trials,
                           seed=cell.seed, chunk=cell.chunk,
                           feedback_beta=cell.feedback_beta,
                           coverage_gamma=cell.coverage_gamma,
                           censored_feedback=cell.censored_feedback,
                           deadline=cell.deadline,
                           deadline_policy=cell.deadline_policy,
                           devices=devices)
        entry = {
            "kind": "rounds", "n": cell.n, "trials": cell.trials,
            "seed": cell.seed, "rounds": cell.rounds, "k": cell.k,
            "deadline": cell.deadline,
            "deadline_policy": cell.deadline_policy,
            "per_round": res.per_round, "stderr": res.stderr,
            "wallclock": res.wallclock,
            "wallclock_stderr": res.wallclock_stderr,
        }
        if res.degradation is not None:
            entry["degradation"] = res.degradation
        results[cell.name] = entry

    seconds = time.perf_counter() - t0
    meta = {"cells": len(cells), "seconds": seconds,
            "cells_per_sec": len(cells) / seconds if seconds > 0 else 0.0,
            "fused_dispatches": len(groups), "buckets": len(sigs),
            "rounds_cells": len(rounds_cells), "pipeline": pipeline,
            "devices": (devices if isinstance(devices, (int, type(None)))
                        else len(tuple(devices)))}
    return GridResult(cells=results, meta=meta)
