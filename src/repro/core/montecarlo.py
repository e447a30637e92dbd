"""Fused batched Monte-Carlo sweep engine — the repo's hot path.

Every paper figure (Figs. 4-7) is an average-completion-time sweep over a
(scheme, r, k, scenario) grid.  The seed code re-sampled delays and re-jitted
a fresh simulation for every scheme at every grid point.  This module
replaces all of that with ONE jitted evaluator that:

1. draws one PRNG subkey **per trial** and samples the delay tensors once
   per scenario — every scheme sees the *same* draws (common random
   numbers), so scheme comparisons are variance-reduced paired samples and
   per-trial completion samples are bit-identical under any chunking of the
   trial axis (chunk-accumulated means agree to float32 round-off);
2. evaluates all stacked TO matrices against the shared draws in one fused
   computation (a single stacked gather + one batched sort);
3. streams trials through ``lax.scan`` in fixed-size chunks, so peak memory
   is O(chunk * n * r) and 10^6+ trials run on a laptop;
4. returns completion times for EVERY k in 1..n from one sort of the task
   arrivals (a whole Fig.-7 k-sweep is one call), while single-k queries
   take one order statistic by a sort-free rank count (``_kth_smallest``);
5. computes task arrival times with a statically precomputed gather +
   min-reduction (each task's copy positions are known from the TO matrix
   at trace time) instead of a dynamic scatter-min — the TPU-friendly form.

Scheme kinds
------------
* ``"to"``   — a TO matrix ``C``: order statistics of the per-task arrival
               times (paper eqs. 1-2, 6).
* ``"lb"``   — the oracle lower bound at load ``r``: order statistics over
               all ``n*r`` slot arrivals (eq. 46).
* ``"pc"``   — polynomially-coded workers at load ``r``: the
               ``2*ceil(n/r)-1``-th order statistic of the per-worker
               single-message times (eqs. 51-52).  Like ``pcmm``, always a
               single column at the scheme's own decode threshold — the
               sweep's ``k`` never applies to coded schemes.
* ``"pcmm"`` — PC multi-message at load ``r``: the ``2n-1``-th order
               statistic over all slot arrivals (eqs. 56-57).
* ``"tau"``  — raw (unsorted) per-task arrival times, for estimators that
               need the joint distribution (e.g. Theorem 1's H_S).
* ``"adaptive"`` — a base TO matrix whose rows are re-assigned to workers
               every round from observed delay feedback (greedy
               least-covered-first; ``repro.core.scheduling``).  Only
               meaningful with a rounds axis: see ``sweep_rounds``.

Specs with smaller loads than the widest scheme in a sweep simply use the
leading slots of the shared delay tensors (delay statistics are
order-independent, paper Remark 6) — that is what makes cross-``r``
comparisons paired as well.

Intra-round message axis (paper Sec. V-C)
-----------------------------------------
Every spec carries a ``messages`` knob: how many messages each worker sends
per round.  The worker's ``r`` sequential slots are partitioned into
``messages`` consecutive groups; a group's results all become available when
its *closing* slot's computation finishes plus one per-message communication
delay — the ``T2`` draw at the closing slot (``cluster.message_comm_delays``),
so draws stay paired across ``messages`` values under common random numbers.

* ``messages = load`` (the default for ``to``/``tau``/``adaptive``/``lb``/
  ``pcmm``) — full multi-message: each slot is its own message, reproducing
  eq. (1)'s per-slot arrivals ``cumsum(T1) + T2`` bit-exactly (the engine's
  established semantics).
* ``messages = 1`` (the default — and only legal value — for ``pc``) — the
  one-shot semantics: every result of worker ``i`` arrives at
  ``sum_j T1[i, :] + T2[i, r-1]``, exactly the per-worker time PC has always
  used (eqs. 51-52).
* intermediate ``m`` interpolates the communication/computation latency
  trade-off of Ozfatura et al. (arXiv:2004.04948) for the uncoded schemes;
  for ``pcmm`` the master decodes once 2n-1 *partials* arrived, messages
  delivering their group's partials in a lump (eqs. 56-57 generalized).

The remap is static (``message_slot_map``) and folds into the task gather
plans, so the hot path gains zero runtime ops and ``m = load`` compiles to
the identical program as before the axis existed.  A per-message protocol
overhead ``comm_eps`` (Ozfatura et al.'s communication/computation
trade-off: a worker's l-th message arrives ``(l+1) * comm_eps`` late, a
serialized-uplink model) likewise folds into the plans as static offsets,
so an *optimal* message budget exists instead of ``m = load`` always
winning.

Ragged per-worker loads
-----------------------
Every uncoded spec (``to``/``tau``/``adaptive``/``lb``) accepts a
per-worker load vector ``loads`` (``loads[w] <= r_max``): the slot grid
stays rectangular ``(n, r_max)`` — masked trailing slots still consume
delay draws, keeping draws paired under common random numbers across load
vectors — but masked slots are *statically* dropped from the task gather
plans (they read the +inf sentinel), so the hot path gains zero runtime
ops and a uniform ``loads`` is bit-exact with the dense path.  TO matrices
may equivalently carry the raggedness themselves via trailing
``scheduling.MASKED`` (-1) sentinels; message budgets become per-worker
(worker ``w`` sends ``min(messages, loads[w])`` messages).

``adaptive_spec(..., rebalance=True)`` additionally re-allocates whole
slots between workers each round inside the rounds scan
(``greedy_load_rebalance_batch``, Egger et al. arXiv:2304.08589): the
dense base matrix's width is the per-worker cap, ``loads`` the initial
budget, and each round's per-worker loads are recomputed from the same
(optionally censored) delay estimates that drive the row re-assignment —
slow workers shed slots to fast ones under the fixed total budget.

Rounds axis (``sweep_rounds``)
------------------------------
Training runs are sequences of rounds, and real stragglers persist across
them (``repro.core.cluster``).  ``sweep_rounds`` scans a stateful
``DelayProcess`` over ``R`` rounds *inside* the jitted evaluator, carrying
per-trial straggler state (and, for adaptive schemes, per-trial feedback
state), so one call yields full wall-clock trajectories for every scheme
under common random numbers: per-round mean completion times and
cumulative wall-clock curves of shape ``(rounds,)``, or raw per-trial
trajectories ``(trials, rounds)`` via ``trajectory_samples``.

Trace recording and replay (``repro.core.trace``)
-------------------------------------------------
``sweep_rounds``/``trajectory_samples`` accept ``record_trace=True`` to
also stream the realized per-(round, trial, worker, slot) delay tables out
of the scan as a ``DelayTrace``; a ``TraceProcess`` built on that trace
replays it through the same ``init``/``step`` API — keys are ignored and
the per-trial tables ride on the engine's global trial ids, so replay is
chunk-invariant and reproduces the recording run's completion times and
adaptive decisions bit-exactly.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..sharding import shard_trials, trial_devices
from .spec import (DEADLINE_POLICIES, _internal, _legacy_warning,
                   validate_deadline)

__all__ = [
    "SchemeSpec", "SweepResult", "RoundsResult", "to_spec", "lb_spec",
    "pc_spec", "pcmm_spec", "tau_spec", "adaptive_spec", "task_gather_plan",
    "task_arrival_times_gather", "message_boundaries", "message_slot_map",
    "message_group_sizes", "sweep", "sweep_rounds",
    "completion_samples", "trajectory_samples", "task_arrival_samples",
    "ResumableSweep", "resumable_sweep",
    "trial_keys", "clear_cache", "cache_stats", "set_cache_capacity",
]

Array = jax.Array
INF = jnp.inf


# --------------------------- scheme specification ----------------------------

@dataclasses.dataclass(frozen=True)
class SchemeSpec:
    """One scheme to evaluate in a sweep. Hashable (C stored as nested
    tuples) so compiled evaluators can be cached across calls."""
    name: str
    kind: str                 # "to" | "lb" | "pc" | "pcmm" | "tau" | "adaptive"
    C: Optional[tuple] = None       # TO matrix for "to"/"tau"/"adaptive"
    r: Optional[int] = None         # computation load for "lb"/"pc"/"pcmm"
    messages: Optional[int] = None  # per-round messages per worker
                                    # (None = the kind's default semantics)
    loads: Optional[tuple] = None   # per-worker loads (None = uniform/dense;
                                    # for rebalance: the initial budget)
    rebalance: bool = False         # adaptive only: re-allocate whole slots
                                    # between workers each round
    comm_eps: float = 0.0           # per-message protocol overhead: a
                                    # worker's l-th message lands (l+1)*eps
                                    # late (serialized uplink)

    def __post_init__(self):
        # no validation here — invalid specs are (and stay) rejected at
        # sweep time by ``_check_specs`` with engine-level context; direct
        # construction is merely deprecated in favor of the factories /
        # ``RoundConfig.to_scheme_spec()``.
        _legacy_warning(
            "SchemeSpec", "call .to_scheme_spec() (or use the to_spec / "
            "tau_spec / adaptive_spec / lb_spec / pc_spec / pcmm_spec "
            "factories)")

    @property
    def load(self) -> int:
        """Width of this scheme's slot grid (the maximum per-worker load;
        for rebalance specs, the per-worker load cap)."""
        if self.kind in ("to", "tau", "adaptive"):
            return len(self.C[0])
        return int(self.r)

    @property
    def n_messages(self) -> int:
        """Messages each worker sends per round.  ``None`` resolves to the
        kind's established semantics: full multi-message (one message per
        slot, eq. 1) for uncoded schemes / lb / pcmm, one-shot for pc.
        Workers with ragged load below the budget send one message per
        active slot."""
        if self.messages is not None:
            return int(self.messages)
        return 1 if self.kind == "pc" else self.load

    def load_vector(self, n: Optional[int] = None) -> np.ndarray:
        """Per-worker loads as an array (uniform when ``loads`` is None).
        ``n`` is required for matrix-less kinds (lb/pc/pcmm)."""
        if self.loads is not None:
            return np.asarray(self.loads, np.int64)
        n_w = len(self.C) if self.C is not None else n
        if n_w is None:
            raise ValueError(f"{self.name}: need n for a matrix-less spec")
        return np.full(n_w, self.load, np.int64)

    def matrix(self) -> np.ndarray:
        return np.asarray(self.C, dtype=np.int64)


def _freeze_matrix(C) -> tuple:
    C = np.asarray(C)
    if C.ndim != 2:
        raise ValueError(f"TO matrix must be 2-D, got shape {C.shape}")
    return tuple(tuple(int(v) for v in row) for row in C)


def _freeze_ragged(C, loads) -> Tuple[tuple, Optional[tuple]]:
    """Canonicalize a (possibly ragged) TO matrix + load vector: masked
    slots hold ``scheduling.MASKED`` in the frozen C, and a uniform
    full-width ``loads`` canonicalizes to ``None`` — the dense
    representation — so uniform-load specs hash/compare/evaluate
    identically to the established dense path."""
    from . import scheduling
    C = np.asarray(C)
    if C.ndim != 2:
        raise ValueError(f"TO matrix must be 2-D, got shape {C.shape}")
    if loads is not None:
        C = scheduling.mask_matrix_loads(C, loads)
    lv = scheduling.loads_of_matrix(C)             # validates trailing masks
    if (lv == C.shape[1]).all():
        return _freeze_matrix(C), None
    return _freeze_matrix(C), tuple(int(v) for v in lv)


def to_spec(name: str, C, messages: Optional[int] = None, *,
            loads=None, comm_eps: float = 0.0) -> SchemeSpec:
    """A TO-matrix scheme (CS / SS / RA / custom).  ``messages`` is the
    per-round message budget (default: one message per slot, eq. 1);
    ``loads`` masks each row's trailing slots (ragged per-worker loads,
    equivalently encoded as trailing -1 sentinels in ``C``); ``comm_eps``
    is the per-message protocol overhead."""
    Cf, lt = _freeze_ragged(C, loads)
    with _internal():
        return SchemeSpec(name=name, kind="to", C=Cf, messages=messages,
                          loads=lt, comm_eps=float(comm_eps))


def tau_spec(name: str, C, messages: Optional[int] = None, *,
             loads=None, comm_eps: float = 0.0) -> SchemeSpec:
    """Raw task-arrival samples for a TO matrix (no order statistics)."""
    Cf, lt = _freeze_ragged(C, loads)
    with _internal():
        return SchemeSpec(name=name, kind="tau", C=Cf, messages=messages,
                          loads=lt, comm_eps=float(comm_eps))


def adaptive_spec(name: str, C, messages: Optional[int] = None, *,
                  loads=None, rebalance: bool = False) -> SchemeSpec:
    """An adaptive scheme: base TO matrix ``C`` whose rows are re-assigned
    to workers each round from observed per-worker delay feedback (only
    valid in ``sweep_rounds``).  ``loads`` makes the base ragged (rows
    carry their loads through the re-permutation); with ``rebalance=True``
    the base must be dense — its width is the per-worker load *cap*,
    ``loads`` the initial budget — and per-worker loads are additionally
    re-balanced each round from the same feedback (slow workers shed whole
    slots to fast ones under the fixed total budget)."""
    if rebalance:
        # the budget stays a budget — do NOT fold it into row masks
        lt = (None if loads is None
              else tuple(int(v) for v in np.asarray(loads, np.int64)))
        with _internal():
            return SchemeSpec(name=name, kind="adaptive",
                              C=_freeze_matrix(C), messages=messages,
                              loads=lt, rebalance=True)
    Cf, lt = _freeze_ragged(C, loads)
    with _internal():
        return SchemeSpec(name=name, kind="adaptive", C=Cf,
                          messages=messages, loads=lt)


def lb_spec(r: Optional[int] = None, name: str = "lb",
            messages: Optional[int] = None, *,
            loads=None, comm_eps: float = 0.0) -> SchemeSpec:
    """Oracle lower bound (eq. 46) at computation load ``r`` (at a reduced
    ``messages`` budget: the oracle bound among schemes sending that many
    messages per round).  ``loads`` generalizes the bound to a per-worker
    load vector: the k-th order statistic over the ``sum(loads)`` active
    slot arrivals."""
    lt = None
    if loads is not None:
        lv = np.asarray(loads, np.int64)
        if lv.ndim != 1 or lv.min() < 1:
            raise ValueError(f"loads must be a vector of positive per-worker "
                             f"loads, got {loads}")
        r = int(lv.max()) if r is None else int(r)
        if lv.max() > r:
            raise ValueError(f"max load {lv.max()} exceeds r={r}")
        if not (lv == r).all():                    # uniform -> canonical dense
            lt = tuple(int(v) for v in lv)
    elif r is None:
        raise ValueError("need a load r (or a loads vector)")
    with _internal():
        return SchemeSpec(name=name, kind="lb", r=int(r), messages=messages,
                          loads=lt, comm_eps=float(comm_eps))


def pc_spec(r: int, name: str = "pc") -> SchemeSpec:
    """Polynomially-coded scheme at load ``r`` — one-shot by construction
    (the PC decoder needs a worker's full sum, eqs. 51-52); use ``pcmm_spec``
    for coded rounds with an intra-round message budget."""
    with _internal():
        return SchemeSpec(name=name, kind="pc", r=int(r))


def pcmm_spec(r: int, name: str = "pcmm",
              messages: Optional[int] = None) -> SchemeSpec:
    """Polynomially-coded multi-message scheme at load ``r``; ``messages``
    bundles its per-slot partials into fewer messages (eqs. 56-57 keep
    counting partials, they just arrive in lumps)."""
    with _internal():
        return SchemeSpec(name=name, kind="pcmm", r=int(r),
                          messages=messages)


def _pc_threshold(n: int, r: int) -> int:
    return 2 * math.ceil(n / r) - 1


def _pcmm_threshold(n: int) -> int:
    return 2 * n - 1


# ----------------------- intra-round message layout --------------------------

def message_boundaries(r: int, messages: int) -> np.ndarray:
    """Closing slot index of each message when ``r`` sequential slots are
    sent in ``messages`` as-even-as-possible consecutive groups (earlier
    messages carry the extra slot when ``messages`` does not divide ``r``).
    The last message always closes at slot ``r - 1``."""
    if int(messages) != messages:
        raise ValueError(f"messages must be an integer, got {messages!r}")
    if not 1 <= int(messages) <= r:
        raise ValueError(f"message budget out of range: need 1 <= messages "
                         f"<= r={r}, got messages={messages}")
    sizes = [len(g) for g in np.array_split(np.arange(r), int(messages))]
    return np.cumsum(sizes, dtype=np.int64) - 1


def message_group_sizes(r: int, messages: int) -> np.ndarray:
    """Number of slots (results / coded partials) each message carries."""
    b = message_boundaries(r, messages)
    return np.diff(np.concatenate([[-1], b])).astype(np.int64)


def message_slot_map(r: int, messages: int) -> np.ndarray:
    """Slot ``j`` -> the closing slot of ``j``'s message: the slot whose
    arrival time (eq. 1 at the closing slot) carries ``j``'s result.
    Identity for ``messages == r`` (every slot is its own message)."""
    b = message_boundaries(r, messages)
    return b[np.searchsorted(b, np.arange(r))]


def _slot_map_of(spec: SchemeSpec) -> Optional[np.ndarray]:
    """The spec's message remap, or None when it is the identity (full
    multi-message) — callers skip the gather entirely in that case, keeping
    the default path bit-identical to the pre-message-axis engine.

    Dense specs get the shared length-``r`` map; ragged specs a per-worker
    ``(n, r)`` map (worker ``w`` groups its ``loads[w]`` active slots into
    ``min(messages, loads[w])`` messages; masked slots keep the identity —
    they are statically dropped from every plan anyway)."""
    m = spec.n_messages
    r = spec.load
    if spec.loads is None:
        return None if m == r else message_slot_map(r, m)
    rows, nontrivial = [], False
    for l in spec.loads:
        mi = min(m, int(l))
        row = np.arange(r, dtype=np.int64)
        row[:l] = message_slot_map(int(l), mi)
        nontrivial |= mi != l
        rows.append(row)
    return np.stack(rows) if nontrivial else None


def _rebalance_remap(spec: SchemeSpec) -> Optional[np.ndarray]:
    """Per-(load, slot) closing-slot table for rebalance specs with a
    message budget.  A rebalanced worker's load is decided per round at
    runtime, so its message grouping cannot be baked into a static plan
    the way ``_slot_map_of`` does for fixed loads; instead row ``l - 1``
    of this ``(cap, cap)`` table maps slot ``j < l`` to the closing slot
    of ``j``'s message when ``l`` active slots are grouped into
    ``min(messages, l)`` messages, and slots at or beyond the load keep
    the identity (they are masked to +inf before the gather, and +inf
    reads itself).  The rounds scan indexes the table by the realized
    per-row load.  ``None`` when the budget is the identity for every
    feasible load (``messages >= cap``, every slot its own message)."""
    if not spec.rebalance:
        return None
    return _rebalance_remap_table(spec.load, spec.n_messages)


def _rebalance_remap_table(cap: int, messages: int) -> Optional[np.ndarray]:
    """The ``(cap, cap)`` load-indexed closing-slot table itself (see
    ``_rebalance_remap``); shared with the live aggregator, whose round
    function applies the same gather to its single realization."""
    if messages >= cap:
        return None
    tab = np.empty((cap, cap), np.int64)
    for l in range(1, cap + 1):
        row = np.arange(cap)
        row[:l] = message_slot_map(l, min(messages, l))
        tab[l - 1] = row
    return tab


def _apply_slot_map(s: Array, mmap: np.ndarray) -> Array:
    """Gather per-message arrivals: ``s`` (..., n, r); ``mmap`` a shared
    length-``r`` map or a per-worker ``(n, r)`` map."""
    mm = jnp.asarray(mmap)
    if mm.ndim == 1:
        return s[..., mm]
    return jnp.take_along_axis(
        s, jnp.broadcast_to(mm, s.shape[:-2] + mm.shape), axis=-1)


def _message_index_grid(spec: SchemeSpec, n: int) -> np.ndarray:
    """(n_w, r) message index (0-based) of each slot's message under the
    spec's budget and load vector (masked slots get index 0 — they are
    never read)."""
    r = spec.load
    m = spec.n_messages
    lv = spec.load_vector(n)
    grid = np.zeros((len(lv), r), np.int64)
    for i, l in enumerate(lv):
        b = message_boundaries(int(l), min(m, int(l)))
        grid[i, :l] = np.searchsorted(b, np.arange(int(l)))
    return grid


def _offsets_flat_of(spec: SchemeSpec, n: int, r_max: int
                     ) -> Optional[np.ndarray]:
    """Static per-slot arrival offsets from the per-message protocol
    overhead ``comm_eps`` (message ``l`` lands ``(l+1) * eps`` late), laid
    out flat over the row-major ``(n_w, r_max)`` slot grid plus the +inf
    sentinel position (offset 0).  ``None`` when ``eps == 0`` so the
    established zero-overhead path stays bit-identical."""
    if not spec.comm_eps:
        return None
    grid = _message_index_grid(spec, n)                   # (n_w, r)
    n_w, r = grid.shape
    smap = _slot_map_of(spec)
    if smap is None:
        smap = np.broadcast_to(np.arange(r), (n_w, r))
    elif smap.ndim == 1:
        smap = np.broadcast_to(smap, (n_w, r))
    off = np.zeros(n_w * r_max + 1, np.float32)
    # write each message's offset at its *closing* slot (the position the
    # plans gather); all slots of a message share one closing slot + index.
    for i in range(n_w):
        for j in range(r):
            off[i * r_max + int(smap[i, j])] = spec.comm_eps * (grid[i, j] + 1)
    return off


# ------------------- static gather layout for task arrivals ------------------

def task_gather_plan(C, n: int, r_max: Optional[int] = None,
                     slot_map: Optional[np.ndarray] = None) -> np.ndarray:
    """Precompute, at trace time, where every task's copies live.

    Returns an ``(n, m)`` int32 array of *flat* slot indices into the
    row-major ``(n_w, r_max)`` slot grid, where ``m`` is the maximum copy
    multiplicity.  Rows are padded with the sentinel ``n_w * r_max``, which
    callers map to +inf, so ``min`` over the gathered values reproduces the
    scatter-min of eq. (2) with a static gather — the TPU-friendly form.

    ``C`` may be ragged: slots holding the ``scheduling.MASKED`` (-1)
    sentinel are statically dropped from the plan (their grid positions
    read as +inf through the pad), so ragged loads cost zero extra runtime
    ops in the hot path.

    ``slot_map`` (length-``r`` shared, or per-worker ``(n_w, r)``, values
    in ``[0, r)``) redirects slot ``j``'s read to ``slot_map[j]`` — the
    multi-message layout folds its closing-slot remap (``message_slot_map``)
    into the plan, so per-message arrivals cost no extra runtime ops.
    """
    C = np.asarray(C)
    n_w, r = C.shape
    r_max = r if r_max is None else int(r_max)
    if r > r_max:
        raise ValueError(f"TO matrix load r={r} exceeds slot grid r_max={r_max}")
    if slot_map is None:
        slot_map = np.broadcast_to(np.arange(r), (n_w, r))
    else:
        slot_map = np.asarray(slot_map)
        if slot_map.ndim == 1:
            slot_map = np.broadcast_to(slot_map, (n_w, r))
        if (slot_map.shape != (n_w, r) or slot_map.min() < 0
                or slot_map.max() >= r):
            raise ValueError(f"slot_map must be ({r},) or ({n_w}, {r}) with "
                             f"values in [0, {r}); got shape {slot_map.shape}")
    sentinel = n_w * r_max
    positions: list[list[int]] = [[] for _ in range(n)]
    for i in range(n_w):
        for j in range(r):
            if C[i, j] < 0:            # MASKED slot: statically dropped
                continue
            positions[int(C[i, j])].append(i * r_max + int(slot_map[i, j]))
    m = max((len(p) for p in positions), default=0) or 1
    plan = np.full((n, m), sentinel, dtype=np.int32)
    for p, lst in enumerate(positions):
        plan[p, :len(lst)] = lst
    return plan


def task_arrival_times_gather(plan: np.ndarray, s: Array,
                              offsets: Optional[np.ndarray] = None) -> Array:
    """eq. (2) via the static gather plan.

    ``s`` has shape (..., n_w, r_max); ``plan`` may be ``(n, m)`` for one
    scheme or ``(S, n, m)`` for a stack, giving (..., n) or (..., S, n).
    Tasks never assigned come out +inf, matching the scatter-min version.
    ``offsets`` (same shape as ``plan``) adds static per-copy arrival
    offsets (the ``comm_eps`` per-message overhead) before the min.
    """
    sf = s.reshape(s.shape[:-2] + (-1,))
    pad = jnp.full(sf.shape[:-1] + (1,), INF, s.dtype)
    sp = jnp.concatenate([sf, pad], axis=-1)
    g = sp[..., jnp.asarray(plan)]
    if offsets is not None:
        g = g + jnp.asarray(offsets)
    return jnp.min(g, axis=-1)


def _plan_of(spec: SchemeSpec, n: int, r_max: int) -> np.ndarray:
    return task_gather_plan(spec.matrix(), n, r_max,
                            slot_map=_slot_map_of(spec))


def _plan_offsets_of(spec: SchemeSpec, plan: np.ndarray, n: int,
                     r_max: int) -> Optional[np.ndarray]:
    """Per-copy offsets aligned with ``plan`` (``comm_eps`` folded into the
    static layout), or None when the spec has no overhead."""
    off_flat = _offsets_flat_of(spec, n, r_max)
    if off_flat is None:
        return None
    return off_flat[plan]


def _stack_plans(specs: Sequence[SchemeSpec], n: int, r_max: int
                 ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    plans = [_plan_of(sp, n, r_max) for sp in specs]
    m = max(p.shape[1] for p in plans)
    sentinel = n * r_max
    out = np.full((len(plans), n, m), sentinel, dtype=np.int32)
    for i, p in enumerate(plans):
        out[i, :, :p.shape[1]] = p
    offs = None
    if any(sp.comm_eps for sp in specs):
        offs = np.zeros((len(plans), n, m), dtype=np.float32)
        for i, (sp, p) in enumerate(zip(specs, plans)):
            o = _plan_offsets_of(sp, p, n, r_max)
            if o is not None:
                offs[i, :, :p.shape[1]] = o
    return out, offs


# ----------------------------- fused evaluator -------------------------------

def _smallest(x: Array, k: int) -> Array:
    """The k smallest entries of x along the last axis, ascending — a
    partial selection via ``lax.top_k`` (no full O(L log L) sort)."""
    return -jax.lax.top_k(-x, k)[0]


#: widest last axis whose k-th order statistic is taken by rank counting.
#: On a TPU v5e, ``top_k`` and ``jnp.sort`` lower to a (value, index) sort
#: whatever k is, and the rank count's O(L^2) compares beat it only on
#: narrow axes.  One v5e, the 16th smallest of f32[262144, 5, L], ms per
#: call, rank count against top_k: 1.37 / 1.82 at L = 16, 3.26 / 4.01 at
#: 32, 9.30 / 8.57 at 64, 87.3 / 26.7 at 128.
_RANK_COUNT_MAX_WIDTH = 32


def _by_rank_count(width: int) -> bool:
    return width <= _RANK_COUNT_MAX_WIDTH


def _kth_smallest(x: Array, k) -> Array:
    """The exact k-th smallest entry of x along the last axis, shape
    ``(..., 1)``; ``k`` (1-based) is a Python int or an int array that
    broadcasts against ``x[..., :1]``.

    On a narrow axis this is a fused rank count, not a sort: entry i's rank
    is ``#{j: x_j < x_i} + #{j < i: x_j == x_i}``, so exactly one entry has
    each rank (ties and ``+inf`` sentinels included) and the result equals
    ``sort(x)[..., k-1]`` bit for bit."""
    L = x.shape[-1]
    if not _by_rank_count(L):
        if isinstance(k, int):
            return _smallest(x, k)[..., -1:]
        srt = jnp.sort(x, axis=-1)
        return jnp.take_along_axis(
            srt, jnp.broadcast_to(k - 1, srt.shape[:-1] + (1,)), axis=-1)
    xi, xj = x[..., :, None], x[..., None, :]
    before = np.tril(np.ones((L, L), bool), -1)             # [i, j]: j < i
    rank = ((xj < xi) | ((xj == xi) & before)).sum(-1, dtype=jnp.int32)
    return jnp.max(jnp.where(rank == k - 1, x, -INF), axis=-1, keepdims=True)


def _stat_width(spec: SchemeSpec, n: int, ks: Optional[int]) -> int:
    if spec.kind in ("pc", "pcmm"):        # fixed decode thresholds
        return 1
    if spec.kind == "tau":
        return n
    return n if ks is None else 1


def _flat_window_key(sp: SchemeSpec) -> tuple:
    return (sp.load, sp.n_messages, sp.loads, sp.comm_eps)


def _build_eval(specs: Tuple[SchemeSpec, ...], n: int, r_max: int,
                ks: Optional[int], deadline: Optional[float] = None):
    """Static-scheme evaluator: slot arrivals ``s`` (chunk, n, r_max) ->
    {name: (chunk, L)}.  All static structure (gather plans, thresholds,
    slot windows, ragged-load masks, per-message overhead offsets) is baked
    in at trace time; shared by the single-round sampler and the
    rounds-axis scan body.

    With ``deadline`` set the evaluator additionally returns per-scheme
    arrival counts ``{name: (by_deadline, deliverable)}`` (each (chunk,)
    float32): how many distinct results arrive by the deadline, and how
    many would *ever* arrive (finite arrival — fault censoring makes this
    < n).  Coded schemes decode all-or-nothing, so their counts are n or
    0; the oracle bound counts slot arrivals capped at n."""
    to_specs = tuple(sp for sp in specs if sp.kind == "to")
    plan_stack = off_stack = None
    if to_specs:
        plan_stack, off_stack = _stack_plans(to_specs, n, r_max)

    # lb/pcmm both rank the same flattened per-message-arrival window; group
    # them by (load, messages, loads, eps) so each distinct window is
    # selected exactly once.  Dense zero-overhead full-multi-message windows
    # slice the shared slot grid directly (the pre-message-axis code path,
    # bit-identical); dense reduced budgets gather through the shared
    # closing-slot remap; ragged loads and/or overheads use a static flat
    # gather over the active slots only.
    flat_width: Dict[tuple, int] = {}
    flat_spec: Dict[tuple, SchemeSpec] = {}
    for sp in specs:
        if sp.kind == "lb":
            need = n if ks is None else ks
        elif sp.kind == "pcmm":
            need = _pcmm_threshold(n)
        else:
            continue
        key = _flat_window_key(sp)
        flat_width[key] = max(flat_width.get(key, 0), need)
        flat_spec[key] = sp

    def _flat_window(sp: SchemeSpec, s: Array) -> Array:
        r, m = sp.load, sp.n_messages
        if sp.loads is None and not sp.comm_eps:
            if m == r:
                return s[..., :, :r].reshape(s.shape[0], -1)
            return s[..., :, jnp.asarray(message_slot_map(r, m))].reshape(
                s.shape[0], -1)
        # ragged loads and/or per-message overhead: static gather over the
        # active (remapped) slots, plus their static offsets.
        lv = sp.load_vector(n)
        smap = _slot_map_of(sp)
        if smap is None:
            smap = np.broadcast_to(np.arange(r), (n, r))
        elif smap.ndim == 1:
            smap = np.broadcast_to(smap, (n, r))
        idx = np.asarray([i * r_max + int(smap[i, j])
                          for i in range(n) for j in range(int(lv[i]))],
                         np.int32)
        sf = s.reshape(s.shape[0], -1)
        win = sf[..., jnp.asarray(idx)]
        off_flat = _offsets_flat_of(sp, n, r_max)
        if off_flat is not None:
            win = win + jnp.asarray(off_flat[idx])
        return win

    # numpy (not jnp) scalars: builders run eagerly, and plain literals
    # fold into the traced program identically on every device, whereas a
    # concrete jax scalar closed over here is a device-resident buffer
    # (see the matching note in ``_build_rounds_fn``).  Both promote
    # identically in float32 arithmetic.
    DL = None if deadline is None else np.float32(deadline)
    nf = np.float32(n)

    def eval_fn(s: Array):
        out: Dict[str, Array] = {}
        cnts: Dict[str, Tuple[Array, Array]] = {}

        if to_specs:
            tau = task_arrival_times_gather(plan_stack, s, off_stack)
            if ks is None:
                stat = jnp.sort(tau, axis=-1)                # all k at once
            else:
                stat = _kth_smallest(tau, ks)                # k-th only
            if DL is not None:
                by_s = (tau <= DL).sum(-1).astype(jnp.float32)
                dv_s = jnp.isfinite(tau).sum(-1).astype(jnp.float32)
            for i, sp in enumerate(to_specs):
                out[sp.name] = stat[:, i]
                if DL is not None:
                    cnts[sp.name] = (by_s[:, i], dv_s[:, i])

        flat_stats = {}
        flat_cnts = {}
        for key, w in flat_width.items():
            win = _flat_window(flat_spec[key], s)
            flat_stats[key] = _smallest(win, w)      # (chunk, w) ascending
            if DL is not None:
                # oracle: first however-many received are distinct, so the
                # realized count is the slot-arrival count capped at n
                flat_cnts[key] = (
                    jnp.minimum((win <= DL).sum(-1), n).astype(jnp.float32),
                    jnp.minimum(jnp.isfinite(win).sum(-1),
                                n).astype(jnp.float32))

        for sp in specs:
            if sp.kind == "tau":
                plan = _plan_of(sp, n, r_max)
                out[sp.name] = task_arrival_times_gather(
                    plan, s, _plan_offsets_of(sp, plan, n, r_max))
            elif sp.kind == "lb":
                fs = flat_stats[_flat_window_key(sp)]
                out[sp.name] = fs[..., :n] if ks is None else fs[..., ks - 1:ks]
                if DL is not None:
                    cnts[sp.name] = flat_cnts[_flat_window_key(sp)]
            elif sp.kind == "pc":
                r = sp.load
                tw = s[..., r - 1]         # = sum_j T1[..., :r] + T2[..., r-1]
                if sp.comm_eps:
                    tw = tw + jnp.float32(sp.comm_eps)   # its single message
                th = _pc_threshold(n, r)   # PC's own decode threshold — the
                out[sp.name] = _kth_smallest(tw, th)         # sweep k never
                # applies to coded schemes (same rule as pcmm below)
            elif sp.kind == "pcmm":
                th = _pcmm_threshold(n)
                out[sp.name] = flat_stats[_flat_window_key(sp)][
                    ..., th - 1:th]
            if DL is not None and sp.kind in ("pc", "pcmm"):
                # coded decode is all-or-nothing: the full gradient (all n
                # tasks' worth) or nothing usable by the deadline
                v0 = out[sp.name][..., -1]
                cnts[sp.name] = (jnp.where(v0 <= DL, nf, 0.0),
                                 jnp.where(jnp.isfinite(v0), nf, 0.0))
        if DL is None:
            return out
        return out, cnts

    return eval_fn


# --------------------- shape-bucketed runtime evaluator ----------------------
#
# ``_build_eval`` above bakes every gather plan into the traced program, so
# its compile cache key is the full frozen spec tuple — fine for a handful
# of figures, hopeless for a grid sweep where hundreds of cells differ only
# in their TO matrices / budgets / overheads.  The single-round hot path
# therefore uses the *bucketed* twin below: all static structure (gather
# plans, flat-window indices, message offsets, decode thresholds) becomes
# runtime int32/float32 arrays with shapes padded to a small signature
# ``(n, r_max, ks, per-group counts, padded widths)``, so every cell in the
# same shape bucket shares one executable.  Padding is value-exact: padded
# plan entries read the +inf sentinel (transparent to min / top_k), padded
# offsets are 0.0 (``x + 0.0`` is bitwise ``x`` for delays), and the pc
# order statistic is taken by rank at a runtime threshold — so the
# bucketed path is bit-exact with the per-spec path under CRN.
# (``_build_eval`` stays as-is for the rounds axis, whose adaptive scan
# re-evaluates baked static specs every round.)

_GROUPS = ("to", "tau", "lb", "pcmm", "pc")


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 2 ** (x - 1).bit_length()


def _flat_indices_of(sp: SchemeSpec, n: int, r_max: int):
    """Flat indices of the spec's active (message-remapped) slots in the
    row-major ``(n, r_max)`` grid — the runtime form of ``_build_eval``'s
    lb/pcmm flat window — plus their static ``comm_eps`` offsets (None when
    the spec has no overhead)."""
    r = sp.load
    lv = sp.load_vector(n)
    smap = _slot_map_of(sp)
    if smap is None:
        smap = np.broadcast_to(np.arange(r), (n, r))
    elif smap.ndim == 1:
        smap = np.broadcast_to(smap, (n, r))
    idx = np.asarray([i * r_max + int(smap[i, j])
                      for i in range(n) for j in range(int(lv[i]))],
                     np.int32)
    off_flat = _offsets_flat_of(sp, n, r_max)
    if off_flat is None:
        return idx, None
    return idx, off_flat[idx].astype(np.float32)


def _eval_layout(specs: Tuple[SchemeSpec, ...], n: int, r_max: int,
                 ks: Optional[int]):
    """Split one sweep's specs into the fixed evaluator groups and
    materialize every per-spec static structure as *runtime* numpy arrays
    padded to the bucket signature.  Returns ``(sig, params, slots)``:

    * ``sig``    — the hashable shape bucket ``("v1", n, r_max, ks,
      S_to, M_to, S_tau, M_tau, F_lb, F_pcmm, P_pc)``; the compiled
      program depends only on this (plus model and devices).
    * ``params`` — ``{name: numpy array}`` fed to the jitted scans at call
      time (gather plans + offsets per group, flat windows, pc slots /
      thresholds / overheads).
    * ``slots``  — ``{scheme name: (group, index)}``: where each scheme's
      columns live in the group-stacked outputs.  Group-keyed (not
      name-keyed) outputs keep the scan's pytree structure independent of
      scheme names, so renamed cells never retrace.
    """
    W = n * r_max                     # flat slot-grid width; sentinel = W
    by: Dict[str, list] = {g: [] for g in _GROUPS}
    slots: Dict[str, Tuple[str, int]] = {}
    for sp in specs:
        slots[sp.name] = (sp.kind, len(by[sp.kind]))
        by[sp.kind].append(sp)

    params: Dict[str, np.ndarray] = {}

    def _plan_group(group):
        gspecs = by[group]
        if not gspecs:
            return 0, 1
        plans = [_plan_of(sp, n, r_max) for sp in gspecs]
        m = _next_pow2(max(p.shape[1] for p in plans))
        plan = np.full((len(gspecs), n, m), W, np.int32)
        offs = np.zeros((len(gspecs), n, m), np.float32)
        for i, (sp, p) in enumerate(zip(gspecs, plans)):
            plan[i, :, :p.shape[1]] = p
            o = _plan_offsets_of(sp, p, n, r_max)
            if o is not None:
                offs[i, :, :p.shape[1]] = o
        params[group + "_plan"] = plan
        params[group + "_off"] = offs
        return len(gspecs), m

    S_to, M_to = _plan_group("to")
    S_tau, M_tau = _plan_group("tau")

    def _flat_group(group):
        gspecs = by[group]
        if not gspecs:
            return 0
        idx = np.full((len(gspecs), W), W, np.int32)   # sentinel -> +inf
        offs = np.zeros((len(gspecs), W), np.float32)
        for i, sp in enumerate(gspecs):
            fi, fo = _flat_indices_of(sp, n, r_max)
            idx[i, :len(fi)] = fi
            if fo is not None:
                offs[i, :len(fi)] = fo
        params[group + "_idx"] = idx
        params[group + "_off"] = offs
        return len(gspecs)

    F_lb = _flat_group("lb")
    F_pcmm = _flat_group("pcmm")

    pc = by["pc"]
    if pc:
        params["pc_slot"] = np.asarray([sp.load - 1 for sp in pc], np.int32)
        params["pc_th"] = np.asarray(
            [_pc_threshold(n, sp.load) - 1 for sp in pc], np.int32)
        params["pc_eps"] = np.asarray([sp.comm_eps for sp in pc], np.float32)

    sig = ("v1", n, r_max, ks, S_to, M_to, S_tau, M_tau, F_lb, F_pcmm,
           len(pc))
    return sig, params, slots


def _build_bucket_eval(sig):
    """Runtime-parameterized evaluator for one shape bucket: slot arrivals
    ``s`` (chunk, n, r_max) + ``params`` -> {group: (chunk, S_g, L_g)}.
    Value-exact with ``_build_eval`` spec-by-spec (see the bucketing note
    above)."""
    _, n, r_max, ks, S_to, M_to, S_tau, M_tau, F_lb, F_pcmm, P_pc = sig

    def eval_fn(s: Array, params) -> Dict[str, Array]:
        out: Dict[str, Array] = {}
        if F_lb or F_pcmm:
            sf = s.reshape(s.shape[0], -1)
            s_pad = jnp.concatenate(
                [sf, jnp.full(sf.shape[:-1] + (1,), INF, s.dtype)], axis=-1)
        if S_to:
            tau = task_arrival_times_gather(
                params["to_plan"], s, params["to_off"])
            out["to"] = (jnp.sort(tau, axis=-1) if ks is None
                         else _kth_smallest(tau, ks))
        if S_tau:
            out["tau"] = task_arrival_times_gather(
                params["tau_plan"], s, params["tau_off"])
        if F_lb:
            win = s_pad[:, params["lb_idx"]] + params["lb_off"]
            out["lb"] = (_smallest(win, n) if ks is None
                         else _kth_smallest(win, ks))
        if F_pcmm:
            win = s_pad[:, params["pcmm_idx"]] + params["pcmm_off"]
            out["pcmm"] = _kth_smallest(win, _pcmm_threshold(n))
        if P_pc:
            # per-worker one-shot times at each pc spec's own closing slot,
            # ranked at the spec's decode threshold, a runtime value since
            # it varies with the load.
            tw = jnp.moveaxis(s[..., params["pc_slot"]], -1, -2)
            tw = tw + params["pc_eps"][:, None]            # (chunk, P, n)
            out["pc"] = _kth_smallest(tw, params["pc_th"][:, None] + 1)
        return out

    return eval_fn


def _rank_count_columns(sig) -> int:
    """Scheme columns per trial whose statistic ``_build_bucket_eval``
    takes by rank count (``engine.select_rows`` counts them per row)."""
    _, n, r_max, ks, S_to, _, _, _, F_lb, F_pcmm, P_pc = sig
    cols = P_pc if _by_rank_count(n) else 0
    if ks is not None and _by_rank_count(n):
        cols += S_to
    if _by_rank_count(n * r_max):
        cols += F_pcmm + (F_lb if ks is not None else 0)
    return cols


def _build_stats_fn(sig, model):
    """Per-chunk bucketed evaluator: (chunk, 2) per-trial keys + runtime
    ``params`` -> {group: (chunk, S, L)}.  Samples one round of delays per
    trial and scores every scheme of the bucket."""
    n, r_max = sig[1], sig[2]
    eval_fn = _build_bucket_eval(sig)

    def stats_fn(keys: Array, params) -> Dict[str, Array]:
        def one(kk):
            T1, T2 = model.sample(kk, 1, n, r_max)
            return T1[0], T2[0]

        T1, T2 = jax.vmap(one)(keys)                 # (chunk, n, r_max)
        s = jnp.cumsum(T1, axis=-1) + T2             # slot arrivals, eq. (1)
        return eval_fn(s, params)

    return stats_fn


# ----------------------- executor caches + observability ----------------------

class _LRUCache:
    """Least-recently-used bound on the compiled-executor caches.  Once a
    grid sweeps many ``(n, r_max)`` buckets (or many device tuples) an
    unbounded dict would pin every executable ever compiled; the default
    capacity comfortably holds a full grid's buckets while letting one-off
    shapes age out.  Also the home of the cache observability counters
    surfaced by ``cache_stats()``."""

    def __init__(self, capacity: int = 128):
        self.capacity = int(capacity)
        self._d: "collections.OrderedDict" = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_s = 0.0

    def get(self, key):
        hit = self._d.get(key)
        if hit is None:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return hit

    def put(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        self._trim()

    def set_capacity(self, capacity: int) -> None:
        capacity = int(capacity)
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._trim()

    def _trim(self) -> None:
        while len(self._d) > self.capacity:
            self._d.popitem(last=False)            # evict least recent
            self.evictions += 1

    def __len__(self) -> int:
        return len(self._d)

    def clear(self) -> None:
        self._d.clear()

    def stats(self) -> dict:
        return {"size": len(self._d), "capacity": self.capacity,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "compile_s": round(self.compile_s, 6)}


_EXEC_CACHE = _LRUCache()
_TRACE_COUNT = 0


def _count_trace() -> None:
    """Called at the top of every scan function: the call executes during
    tracing only, i.e. once per jit specialization, so the counter measures
    (re)traces — exactly one per shape bucket when the bucketed cache is
    doing its job (pinned by the grid retrace test)."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def _timed_first(fn, cache: _LRUCache):
    """Attribute the first call's wall time to ``cache.compile_s``: tracing
    and compilation happen synchronously inside the first call while the
    actual execution is dispatched asynchronously, so first-call wall time
    is a faithful (slightly conservative) compile-seconds estimate."""
    done = False

    def wrapped(*args):
        nonlocal done
        if done:
            return fn(*args)
        t0 = time.perf_counter()
        out = fn(*args)
        cache.compile_s += time.perf_counter() - t0
        done = True
        return out

    return wrapped


def clear_cache() -> None:
    """Drop compiled evaluators (mainly for benchmarking cold starts)."""
    _EXEC_CACHE.clear()
    _ROUNDS_CACHE.clear()


def set_cache_capacity(capacity: int) -> None:
    """Bound both compiled-executor LRU caches to ``capacity`` entries
    (evicting the least-recently-used immediately if already over)."""
    _EXEC_CACHE.set_capacity(capacity)
    _ROUNDS_CACHE.set_capacity(capacity)


def cache_stats() -> dict:
    """Observability for the compiled-executor caches: sizes, hit / miss /
    eviction counts, cumulative compile seconds, and ``traces`` — the
    number of executor (re)traces since import (one per shape bucket when
    the bucketed cache works; see ``_count_trace``)."""
    return {"exec": _EXEC_CACHE.stats(), "rounds": _ROUNDS_CACHE.stats(),
            "traces": _TRACE_COUNT}


def _normalize_chunk(trials: int, chunk: Optional[int]) -> int:
    """Canonical ``chunk`` normalization shared by every sweep entry point.
    ``None`` means one chunk; anything outside ``1..trials`` is an error
    (an oversized chunk used to be silently clamped, which hid typos and,
    under shard padding, would burn whole padded chunks per device)."""
    if trials < 1:
        raise ValueError(f"trials must be >= 1, got {trials}")
    if chunk is None:
        return trials
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got chunk={chunk}")
    if chunk > trials:
        raise ValueError(
            f"chunk ({chunk}) exceeds trials ({trials}); pass chunk <= "
            f"trials (or chunk=None for a single chunk)")
    return chunk


def _shard_layout(trials: int, chunk: int, devices):
    """Device/padding layout of a sharded sweep.

    The global trial axis is cut into ``ceil(trials / chunk)`` chunks (the
    same decomposition for ANY device count — that is what keeps sharded
    results bit-exact vs. the single-device path), chunks are dealt to
    devices in contiguous blocks, and the chunk count is padded up to a
    multiple of the devices actually used (at most ``d_eff - 1`` padded
    chunks; padded trials repeat real keys and are masked out of every
    statistic).  Returns ``(devs, nc_pad, padded_trials)``.
    """
    devs = trial_devices(devices)
    nc = -(-trials // chunk)                    # global chunks
    d_eff = min(len(devs), nc)
    nc_pad = -(-nc // d_eff) * d_eff
    return devs[:d_eff], nc_pad, nc_pad * chunk


def trial_keys(seed: int, trials: int) -> Array:
    """The engine's per-trial CRN keys: key ``t`` is
    ``fold_in(PRNGKey(seed), t)`` — a pure function of ``(seed, t)``, so
    every chunk of the trial axis re-derives its own keys *device-side*
    from ``(seed, global trial id)`` inside the scans instead of
    materializing a ``(trials, 2)`` key table on the host (800 MB at 10^8
    trials).  This helper is the materialized reference twin the tests pin
    the in-scan derivation against."""
    return _fold_keys(jax.random.PRNGKey(seed),
                      jnp.arange(trials, dtype=jnp.int32))


def _fold_keys(base_key: Array, tids: Array) -> Array:
    """(chunk,) global trial ids -> (chunk, 2) per-trial CRN keys."""
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(base_key, tids)


def _padded_keys(seed: int, trials: int, padded: int) -> Array:
    """``trial_keys`` padded to the shard layout.  Pad rows repeat the last
    real key — exactly what the scans' clamped trial ids derive — and feed
    masked lanes only, so CRN pairing across specs survives any device
    count.  Kept as the tests' reference twin of the scans' in-body
    ``min(start + offs, trials - 1)`` derivation."""
    keys = trial_keys(seed, trials)
    if padded > trials:
        pad = jnp.broadcast_to(keys[-1:], (padded - trials, 2))
        keys = jnp.concatenate([keys, pad], axis=0)
    return keys


def _tree_sum(v: Array) -> Array:
    """Sum over axis 0 through an explicit balanced pairwise tree (zero-pad
    to a power of two, then halve): every add is elementwise, so the f32
    association order is a function of the axis length ALONE — the same
    trial chunk reduces bit-identically whatever the width of the spec
    stack around it (see the bit-exactness note in ``sums_scan``)."""
    m = v.shape[0]
    p = _next_pow2(m)
    if p != m:
        v = jnp.concatenate(
            [v, jnp.zeros((p - m,) + v.shape[1:], v.dtype)], axis=0)
    while v.shape[0] > 1:
        v = v[0::2] + v[1::2]
    return v[0]


def _get_exec(sig: tuple, model, devices: tuple):
    """Compiled (sums-scan, samples-scan) pair for one shape bucket, cached
    per (sig, model, devices) — the signature carries only counts and
    padded widths (see ``_eval_layout``), so every sweep with the same
    scheme-kind structure reuses one executable with its own runtime
    params (the sharded evaluator is mesh-specific, so the device tuple is
    part of the key).

    Both scans derive their per-trial CRN keys device-side from (base key,
    global trial id) via ``fold_in`` — the validity mask folds into the
    same integer arithmetic (``start + offs`` vs ``limit``), so no key
    table or mask is materialized on the host — and emit **per-chunk
    float32 partials** combined on the host in float64 in global chunk
    order, which makes the reduction independent of how chunks are dealt
    to devices: sharded stats are bit-exact vs. single-device."""
    cache_key = None
    try:
        cache_key = (sig, model, devices)
        hit = _EXEC_CACHE.get(cache_key)
        if hit is not None:
            return hit
    except TypeError:              # unhashable custom model: build uncached
        cache_key = None

    stats_fn = _build_stats_fn(sig, model)

    def sums_scan(base_key, starts, offs, limit, params):
        _count_trace()

        def body(carry, start):
            tids_raw = start + offs
            kc = _fold_keys(base_key, jnp.minimum(tids_raw, limit - 1))
            st = stats_fn(kc, params)
            ok = (tids_raw < limit)[:, None, None]
            # the barrier pins the f32 rounding of the masked values and
            # squares BEFORE the trial reduction, and ``_tree_sum`` fixes
            # the reduction's association order as a function of the chunk
            # length alone: a native ``sum(axis=0)`` lets XLA pick a
            # stack-width-dependent lane decomposition (and fuse the
            # square in as an FMA), so the same cell evaluated in two
            # different spec stacks could differ in the last ulp of its
            # partial sums — breaking the grid engine's bit-exactness
            # contract between fused and per-cell sweeps.
            s0 = {g: jnp.where(ok, v, 0.0) for g, v in st.items()}
            s1 = {g: jnp.where(ok, jnp.square(v), 0.0)
                  for g, v in st.items()}
            s0, s1 = jax.lax.optimization_barrier((s0, s1))
            s0 = {g: _tree_sum(v) for g, v in s0.items()}
            s1 = {g: _tree_sum(v) for g, v in s1.items()}
            return carry, (s0, s1)

        with jax.named_scope("engine.sums_scan"):
            _, parts = jax.lax.scan(body, None, starts)
        return parts               # 2 x {group: (nc, S, L)} partials

    def samples_scan(base_key, starts, offs, limit, params):
        _count_trace()

        def body(carry, start):
            tids = jnp.minimum(start + offs, limit - 1)
            return carry, stats_fn(_fold_keys(base_key, tids), params)

        with jax.named_scope("engine.samples_scan"):
            _, ys = jax.lax.scan(body, None, starts)
        return ys                  # {group: (nc, chunk, S, L)}

    if len(devices) > 1:
        # shard_trials returns a fully-jitted callable; no outer jit.
        # Only the per-chunk starts are sharded — the base key, offset
        # vector, trial limit, and runtime eval params replicate.
        exec_ = (shard_trials(sums_scan, devices, replicated=(0, 2, 3, 4)),
                 shard_trials(samples_scan, devices, replicated=(0, 2, 3, 4)))
    else:
        exec_ = (jax.jit(sums_scan), jax.jit(samples_scan))
    exec_ = (_timed_first(exec_[0], _EXEC_CACHE),
             _timed_first(exec_[1], _EXEC_CACHE))
    if cache_key is not None:
        _EXEC_CACHE.put(cache_key, exec_)
    return exec_


def _covered_tasks(sp: SchemeSpec) -> int:
    """Number of distinct tasks a (possibly ragged) TO spec can deliver.
    Row re-permutation never changes the union of active slots, so this is
    permutation-invariant; rebalance specs are validated to have a slot-0
    diagonal covering everything."""
    C = sp.matrix()
    return len(np.unique(C[C >= 0]))


def _check_specs(specs: Sequence[SchemeSpec], n: int) -> Tuple[SchemeSpec, ...]:
    from . import scheduling
    specs = tuple(specs)
    if not specs:
        raise ValueError("need at least one SchemeSpec")
    names = [sp.name for sp in specs]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate scheme names: {names}")
    for sp in specs:
        if sp.kind in ("to", "tau", "adaptive") and len(sp.C) != n:
            raise ValueError(f"{sp.name}: TO matrix has {len(sp.C)} rows, "
                             f"expected n={n}")
        if sp.kind in ("lb", "pc", "pcmm") and not 1 <= sp.load:
            raise ValueError(f"{sp.name}: bad load r={sp.r}")
        if sp.kind == "pcmm" and n * sp.load < _pcmm_threshold(n):
            raise ValueError(
                f"{sp.name}: PCMM infeasible: n*r={n * sp.load} < "
                f"2n-1={_pcmm_threshold(n)}")
        if sp.comm_eps < 0:
            raise ValueError(f"{sp.name}: comm_eps must be >= 0, got "
                             f"{sp.comm_eps}")
        if sp.messages is not None:
            if sp.kind == "pc" and sp.messages != 1:
                raise ValueError(
                    f"{sp.name}: pc is one-shot by construction (the decoder "
                    f"needs each worker's full sum); use pcmm for "
                    f"multi-message coded rounds")
            if not 1 <= sp.messages <= sp.load:
                raise ValueError(
                    f"{sp.name}: need 1 <= messages <= load={sp.load}, got "
                    f"messages={sp.messages}")
        # ---- ragged-load validation -----------------------------------
        if sp.loads is not None:
            if sp.kind in ("pc", "pcmm"):
                raise ValueError(f"{sp.name}: ragged loads are not defined "
                                 f"for coded schemes (the decode threshold "
                                 f"assumes a uniform load)")
            lv = np.asarray(sp.loads, np.int64)
            if lv.shape != (n,) or lv.min() < 1 or lv.max() > sp.load:
                raise ValueError(
                    f"{sp.name}: loads must be ({n},) with 1 <= load <= "
                    f"{sp.load}, got {sp.loads}")
        if sp.kind in ("to", "tau", "adaptive") and not sp.rebalance:
            # masks must be a trailing suffix matching the loads field
            # (spec constructors guarantee this; direct SchemeSpec
            # construction is validated here)
            C = sp.matrix()
            if sp.loads is not None or (C < 0).any():
                scheduling.validate_to_matrix(C, n, loads=sp.loads)
        if sp.rebalance:
            if sp.kind != "adaptive":
                raise ValueError(f"{sp.name}: rebalance is only defined for "
                                 f"adaptive specs")
            C = sp.matrix()
            if (C < 0).any():
                raise ValueError(f"{sp.name}: rebalance needs a dense base "
                                 f"matrix (its width is the load cap)")
            if sp.loads is None:
                raise ValueError(f"{sp.name}: rebalance needs an initial "
                                 f"loads budget below the grid width")
            if sorted(C[:, 0].tolist()) != list(range(n)):
                raise ValueError(
                    f"{sp.name}: rebalance needs a slot-0 diagonal (every "
                    f"row's first task distinct, e.g. CS/SS) so any load "
                    f"vector keeps all tasks covered")
            if sp.comm_eps:
                raise ValueError(f"{sp.name}: rebalance does not support "
                                 f"comm_eps yet")
        elif sp.comm_eps and sp.kind == "adaptive":
            raise ValueError(f"{sp.name}: comm_eps is not supported for "
                             f"adaptive specs yet")
    return specs


class _Pending:
    """A dispatched (in-flight) sweep.  The device work was launched
    asynchronously (JAX async dispatch); ``resolve()`` blocks on the
    transfers and finishes the float64 host combine.  ``stream_grid``
    keeps a small window of these in flight so cell ``j+1``'s compute
    overlaps cell ``j``'s device->host transfer and combine."""

    __slots__ = ("_resolve", "_out", "_done")

    def __init__(self, resolve_fn):
        self._resolve = resolve_fn
        self._out = None
        self._done = False

    def resolve(self):
        if not self._done:
            self._out = self._resolve()
            self._done = True
            self._resolve = None
        return self._out


def _scan_coords(trials: int, chunk: int, nc_pad: int):
    """The scans' runtime trial-axis coordinates: per-chunk global start
    ids (the sharded axis), the in-chunk offset vector (its length carries
    the chunk size into the compiled shape), and the valid-trial limit."""
    starts = jnp.arange(nc_pad, dtype=jnp.int32) * jnp.int32(chunk)
    offs = jnp.arange(chunk, dtype=jnp.int32)
    return starts, offs, jnp.int32(trials)


def _validate_single_round(specs: Sequence[SchemeSpec], n: int,
                           ks: Optional[int]) -> Tuple[SchemeSpec, ...]:
    """Shared validation for the single-round entry points (``sweep``,
    ``completion_samples``, ``ResumableSweep``): spec well-formedness, no
    adaptive specs (those need a rounds axis), target-k range, and task
    coverage (a ragged schedule that cannot deliver ``k`` distinct tasks
    has an infinite completion time)."""
    specs = _check_specs(specs, n)
    for sp in specs:
        if sp.kind == "adaptive":
            raise ValueError(f"{sp.name}: adaptive schemes need a rounds "
                             f"axis — use sweep_rounds")
    if ks is not None and not 1 <= ks <= n:
        raise ValueError(f"need 1 <= k <= n={n}, got k={ks}")
    for sp in specs:
        if sp.kind != "to":
            continue                   # tau: raw arrivals, +inf meaningful
        covered = _covered_tasks(sp)
        if ks is not None and covered < ks:
            raise ValueError(
                f"{sp.name}: ragged schedule covers only {covered} "
                f"distinct tasks < k={ks}; the completion time would be "
                f"infinite")
        if ks is None and covered < n:
            raise ValueError(
                f"{sp.name}: schedule covers only {covered} of {n} tasks, "
                f"so all-k completion times are infinite beyond "
                f"k={covered}; sweep with ks <= {covered} instead")
    return specs


@obs.span("engine.dispatch")
def _dispatch_run(specs: Sequence[SchemeSpec], model, n: int, *, trials: int,
                  seed: int, chunk: Optional[int], ks: Optional[int],
                  want_samples: bool, devices=None) -> _Pending:
    """Validate + launch one sweep without blocking on its results; the
    returned ``_Pending`` resolves to ``_run``'s output."""
    specs = _validate_single_round(specs, n, ks)
    r_max = max(sp.load for sp in specs)
    chunk = _normalize_chunk(trials, chunk)
    devs, nc_pad, padded = _shard_layout(trials, chunk, devices)
    sig, params, slots = _eval_layout(specs, n, r_max, ks)
    jsums, jsamples = _get_exec(sig, model, devs)

    base_key = jax.random.PRNGKey(seed)
    starts, offs, limit = _scan_coords(trials, chunk, nc_pad)
    pj = {k2: jnp.asarray(v) for k2, v in params.items()}
    obs.count("engine.select_rows", padded * _rank_count_columns(sig))

    if want_samples:
        ys = jsamples(base_key, starts, offs, limit, pj)

        def resolve_samples():
            out = {}
            for name, (g, i) in slots.items():
                v = ys[g]                        # (nc, chunk, S, L)
                out[name] = v[:, :, i, :].reshape(padded,
                                                  v.shape[-1])[:trials]
            return out

        return _Pending(resolve_samples)

    p0, p1 = jsums(base_key, starts, offs, limit, pj)

    def resolve_sums():
        with obs.span("engine.wait"):
            jax.block_until_ready((p0, p1))
        with obs.span("engine.combine"):
            obs.count("engine.fetched_bytes",
                      sum(v.nbytes for v in (*p0.values(), *p1.values())))
            # per-chunk float32 partials -> float64 in global chunk order:
            # the same reduction whatever the device count (bit-exact
            # sharding).
            mu_g = {g: np.asarray(v, np.float64).sum(axis=0) / trials
                    for g, v in p0.items()}
            sq_g = {g: np.asarray(v, np.float64).sum(axis=0)
                    for g, v in p1.items()}
            means, stderr = {}, {}
            for name, (g, i) in slots.items():
                mu = mu_g[g][i]
                var = np.maximum(sq_g[g][i] / trials - mu * mu, 0.0)
                means[name] = mu
                stderr[name] = np.sqrt(var / trials)
            return means, stderr

    return _Pending(resolve_sums)


def _run(specs: Sequence[SchemeSpec], model, n: int, *, trials: int,
         seed: int, chunk: Optional[int], ks: Optional[int],
         want_samples: bool, devices=None):
    return _dispatch_run(specs, model, n, trials=trials, seed=seed,
                         chunk=chunk, ks=ks, want_samples=want_samples,
                         devices=devices).resolve()


# ------------------------------- public API ----------------------------------

@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Mean completion times (and MC standard errors) per scheme.

    ``means[name]`` has one column per k in 1..n when the sweep ran in
    all-k mode (``ks=None``), a single column for single-k sweeps and for
    ``pcmm`` (whose threshold ``2n-1`` exceeds ``n``).
    """
    means: Dict[str, np.ndarray]
    stderr: Dict[str, np.ndarray]
    trials: int
    n: int
    ks: Optional[int]
    fixed: frozenset = frozenset()      # pc/pcmm: scheme-defined thresholds

    def at_k(self, name: str, k: Optional[int] = None) -> float:
        """Mean completion time of ``name`` at target ``k``.  Coded schemes
        (``pc``/``pcmm``) always report their own decode threshold, so ``k``
        is ignored for them."""
        if name not in self.means:
            raise ValueError(f"unknown scheme {name!r}; have "
                             f"{sorted(self.means)}")
        v = self.means[name]
        if name in self.fixed:
            return float(v[0])
        if k is None:
            raise ValueError(f"{name} needs an explicit k")
        if v.shape[-1] == self.n:
            if not 1 <= k <= self.n:
                raise ValueError(f"need 1 <= k <= {self.n}, got {k}")
            return float(v[k - 1])
        if self.ks is not None and k != self.ks:
            raise ValueError(f"sweep ran with k={self.ks}; asked for k={k}")
        return float(v[0])


def _reject_single_round_trace(record_trace: bool, fn: str) -> None:
    """Canonical rejection of ``record_trace=`` on the single-round entry
    points (accepted for signature uniformity with the rounds axis)."""
    if record_trace:
        raise ValueError(f"record_trace is only available on the rounds "
                         f"axis (sweep_rounds / trajectory_samples); "
                         f"{fn} evaluates a single round and has no "
                         f"per-round delay tables to record")


@obs.span("engine.sweep")
def sweep(specs: Sequence[SchemeSpec], model, n: int, *, trials: int = 20000,
          seed: int = 0, chunk: Optional[int] = None,
          ks: Optional[int] = None, record_trace: bool = False,
          devices=None, greedy_impl: Optional[str] = None) -> SweepResult:
    """Evaluate every scheme against ONE shared set of delay draws.

    Parameters
    ----------
    specs:  schemes to evaluate (see ``to_spec``/``lb_spec``/...).
    model:  a ``DelayModel``; sampled once per trial with a per-trial subkey.
    n:      number of tasks (= workers in the paper's setting).
    trials: Monte-Carlo rounds.
    chunk:  trials are streamed through ``lax.scan`` in chunks of this size
            (default: one chunk).  The per-trial draws are chunk-invariant,
            so per-trial samples are bit-identical for any chunk size and
            means agree to accumulation round-off; memory is
            O(chunk * n * r_max) per device.
    ks:     ``None`` → all-k mode: one sort yields every k in 1..n.
            An int → only that order statistic (``_kth_smallest``).
    record_trace: accepted for signature uniformity with ``sweep_rounds``;
            single-round sweeps have nothing to record, so ``True`` raises
            a ValueError pointing at the rounds axis.
    devices: shard the trial axis across these devices
            (``None`` = all local devices, an int = that many, or an
            explicit sequence).  Whole chunks are dealt to devices, so at
            most ``min(len(devices), ceil(trials/chunk))`` devices are
            used — pass ``chunk <= trials // len(devices)`` to engage all
            of them.  Results are bit-exact vs. the single-device path for
            the same (trials, seed, chunk).
    greedy_impl: accepted (and validated) for signature uniformity with
            ``sweep_rounds``; single-round sweeps reject adaptive specs,
            so there is no greedy pick loop to route.
    """
    from .scheduling import _resolve_greedy_impl
    _reject_single_round_trace(record_trace, "sweep")
    _resolve_greedy_impl(greedy_impl)
    means, stderr = _run(specs, model, n, trials=trials, seed=seed,
                         chunk=chunk, ks=ks, want_samples=False,
                         devices=devices)
    fixed = frozenset(sp.name for sp in specs if sp.kind in ("pc", "pcmm"))
    return SweepResult(means=means, stderr=stderr, trials=trials, n=n, ks=ks,
                       fixed=fixed)


# ----------------------------- resumable sweeps ------------------------------

class ResumableSweep:
    """A sweep whose trial axis can be *extended* instead of recomputed.

    The engine's per-trial CRN key is a pure function of ``(seed, global
    trial id)`` and its statistics are combined from per-chunk float32
    partials in global chunk order (see ``_get_exec``), so a sweep paused
    at ``t`` trials can continue by dispatching only the chunks covering
    trials ``t..total-1`` with the *same* base key and chunk size: the new
    chunk partials are bit-identical to the corresponding chunks of a
    fresh run at ``total``, and accumulating them after the stored ones
    (pad chunks contribute exact float64 zeros) reproduces a fresh
    ``sweep(..., trials=total)`` bit-for-bit.  That is what lets the
    racing planner (``repro.core.planner``) deepen only the cells whose
    comparison is still close, at zero re-evaluation cost.

    Contract and caveats:

    * ``chunk`` is required — resumability is defined by the chunk
      decomposition.  Every ``extend_trials`` total except the last must
      land on a chunk boundary: a partial final chunk clamps its trailing
      trial ids, so there is no representable continuation past it
      (extending from a non-aligned total raises).
    * ``narrow(names)`` drops schemes from subsequent extensions (the
      planner eliminating cells).  The evaluator keeps the *original*
      slot-grid width ``r_max``: delay draws have shape ``(n, r_max)``
      and CRN pairing across the surviving schemes only holds if that
      shape never changes.  ``_tree_sum`` pins the per-chunk reduction
      order as a function of the chunk length alone, so narrowing the
      spec stack keeps every survivor's partials bit-identical.
    * With ``keep_samples=True`` each extension also dispatches the
      samples scan and stores per-trial float32 statistics host-side
      (memory ``O(done * L)`` per scheme).  The sums path still comes
      from the sums scan: XLA's rounding of the squared statistics in
      the fused sums program is not reproducible from the emitted
      samples (measured: last-ulp differences in all-k mode), so
      deriving partials host-side would break the bit-exactness
      contract.
    """

    def __init__(self, specs: Sequence[SchemeSpec], model, n: int, *,
                 seed: int = 0, chunk: int, ks: Optional[int] = None,
                 devices=None, keep_samples: bool = False):
        specs = _validate_single_round(specs, n, ks)
        chunk = int(chunk)
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got chunk={chunk}")
        self._specs = specs
        self._model = model
        self._n = int(n)
        self._seed = int(seed)
        self._chunk = chunk
        self._ks = ks
        self._devices = devices
        self._keep = bool(keep_samples)
        self._r_max = max(sp.load for sp in specs)
        self._base_key = jax.random.PRNGKey(seed)
        self._done = 0
        self._p0: Dict[str, list] = {sp.name: [] for sp in specs}
        self._p1: Dict[str, list] = {sp.name: [] for sp in specs}
        self._samp: Dict[str, list] = (
            {sp.name: [] for sp in specs} if self._keep else {})

    @property
    def trials(self) -> int:
        """Trials evaluated so far."""
        return self._done

    @property
    def chunk(self) -> int:
        return self._chunk

    @property
    def spec_names(self) -> Tuple[str, ...]:
        return tuple(sp.name for sp in self._specs)

    @obs.span("engine.extend")
    def extend_trials(self, total: int) -> SweepResult:
        """Continue the sweep to ``total`` trials and return the combined
        result — bit-exact with ``sweep(..., trials=total)`` at the same
        (seed, chunk)."""
        total = int(total)
        if total <= self._done:
            raise ValueError(
                f"extend_trials: total ({total}) must exceed the "
                f"{self._done} trials already evaluated")
        if self._done % self._chunk != 0:
            raise ValueError(
                f"extend_trials: current total ({self._done}) is not a "
                f"multiple of chunk ({self._chunk}); a partial final chunk "
                f"clamps its trailing trial ids, so the sweep cannot be "
                f"extended past it (keep every total but the last "
                f"chunk-aligned)")
        with obs.span("engine.dispatch"):
            add = total - self._done
            nc = -(-add // self._chunk)
            devs = trial_devices(self._devices)
            d_eff = min(len(devs), nc)
            nc_pad = -(-nc // d_eff) * d_eff
            sig, params, slots = _eval_layout(self._specs, self._n,
                                              self._r_max, self._ks)
            jsums, jsamples = _get_exec(sig, self._model, devs[:d_eff])
            first = self._done // self._chunk
            starts = ((jnp.arange(nc_pad, dtype=jnp.int32)
                       + jnp.int32(first)) * jnp.int32(self._chunk))
            offs = jnp.arange(self._chunk, dtype=jnp.int32)
            limit = jnp.int32(total)
            pj = {k2: jnp.asarray(v) for k2, v in params.items()}
            p0, p1 = jsums(self._base_key, starts, offs, limit, pj)
            ys = (jsamples(self._base_key, starts, offs, limit, pj)
                  if self._keep else None)
            obs.count("engine.select_rows",
                      (1 + self._keep) * nc_pad * self._chunk
                      * _rank_count_columns(sig))
        with obs.span("engine.wait"):
            jax.block_until_ready((p0, p1, ys))
        with obs.span("engine.combine"):
            h0 = {g: np.asarray(v, np.float32) for g, v in p0.items()}
            h1 = {g: np.asarray(v, np.float32) for g, v in p1.items()}
            fetched = sum(h.nbytes for h in (*h0.values(), *h1.values()))
            for name, (g, i) in slots.items():
                self._p0[name].append(h0[g][:, i, :])
                self._p1[name].append(h1[g][:, i, :])
                if ys is not None:
                    v = ys[g]                  # (nc_pad, chunk, S, L)
                    flat = v[:, :, i, :].reshape(nc_pad * self._chunk,
                                                 v.shape[-1])
                    x = np.asarray(flat[:add], np.float32)
                    fetched += x.nbytes
                    self._samp[name].append(x)
            obs.count("engine.fetched_bytes", fetched)
            self._done = total
            return self.result()

    def result(self) -> SweepResult:
        """Combined result over all trials evaluated so far (same float64
        host combine as ``sweep``, in global chunk order)."""
        if self._done == 0:
            raise ValueError("no trials evaluated yet; call extend_trials")
        t = self._done
        means: Dict[str, np.ndarray] = {}
        stderr: Dict[str, np.ndarray] = {}
        for sp in self._specs:
            s0 = np.concatenate(self._p0[sp.name], axis=0).astype(np.float64)
            s1 = np.concatenate(self._p1[sp.name], axis=0).astype(np.float64)
            mu = s0.sum(axis=0) / t
            var = np.maximum(s1.sum(axis=0) / t - mu * mu, 0.0)
            means[sp.name] = mu
            stderr[sp.name] = np.sqrt(var / t)
        fixed = frozenset(sp.name for sp in self._specs
                          if sp.kind in ("pc", "pcmm"))
        return SweepResult(means=means, stderr=stderr, trials=t, n=self._n,
                           ks=self._ks, fixed=fixed)

    def samples(self) -> Dict[str, np.ndarray]:
        """Per-trial statistics ``{name: (trials, L)}`` accumulated so far
        (CRN-paired across schemes: row ``t`` of every scheme saw the same
        delay draws).  Requires ``keep_samples=True``."""
        if not self._keep:
            raise ValueError("per-trial samples were not kept; construct "
                             "with keep_samples=True")
        return {sp.name: np.concatenate(self._samp[sp.name], axis=0)
                for sp in self._specs}

    def narrow(self, names: Sequence[str]) -> None:
        """Drop every scheme not in ``names`` from subsequent extensions
        (their accumulated state is freed).  The evaluator keeps the
        original ``r_max`` so the survivors' draw coordinates — and hence
        their partials — are unchanged."""
        keep = set(names)
        have = {sp.name for sp in self._specs}
        unknown = sorted(keep - have)
        if unknown:
            raise ValueError(f"narrow: unknown scheme(s) {unknown}; have "
                             f"{sorted(have)}")
        if not keep:
            raise ValueError("narrow: need at least one surviving scheme")
        self._specs = tuple(sp for sp in self._specs if sp.name in keep)
        for d in (self._p0, self._p1, self._samp):
            for nm in list(d):
                if nm not in keep:
                    del d[nm]


def resumable_sweep(specs: Sequence[SchemeSpec], model, n: int, *,
                    seed: int = 0, chunk: int, ks: Optional[int] = None,
                    devices=None, keep_samples: bool = False
                    ) -> ResumableSweep:
    """Construct a ``ResumableSweep`` (see its docstring): a sweep whose
    trial axis extends incrementally via ``extend_trials``, bit-exact with
    a fresh ``sweep`` at the combined trial count under CRN."""
    return ResumableSweep(specs, model, n, seed=seed, chunk=chunk, ks=ks,
                          devices=devices, keep_samples=keep_samples)


def completion_samples(spec: SchemeSpec, model, n: int, *, trials: int = 10000,
                       seed: int = 0, chunk: Optional[int] = None,
                       k: Optional[int] = None, record_trace: bool = False,
                       devices=None,
                       greedy_impl: Optional[str] = None) -> Array:
    """Per-trial completion-time samples for one scheme.

    Returns shape ``(trials,)`` when ``k`` is given (or for ``pcmm``), else
    ``(trials, n)`` with column ``k-1`` holding the k-th order statistic.
    ``record_trace`` / ``greedy_impl`` are accepted for signature
    uniformity with the rounds axis (see ``sweep``).
    """
    from .scheduling import _resolve_greedy_impl
    _reject_single_round_trace(record_trace, "completion_samples")
    _resolve_greedy_impl(greedy_impl)
    out = _run([spec], model, n, trials=trials, seed=seed, chunk=chunk,
               ks=k, want_samples=True, devices=devices)[spec.name]
    return out[:, 0] if out.shape[-1] == 1 else out


def task_arrival_samples(C, model, *, trials: int = 10000, seed: int = 0,
                         chunk: Optional[int] = None,
                         messages: Optional[int] = None,
                         loads=None, comm_eps: float = 0.0,
                         record_trace: bool = False, devices=None,
                         greedy_impl: Optional[str] = None) -> Array:
    """Raw per-task arrival-time samples ``tau`` of shape (trials, n) for a
    TO matrix — shared-draw backing for joint-survival estimators.
    ``messages`` is the per-round message budget (default: per-slot sends);
    ``loads`` masks each row's trailing slots (ragged per-worker loads —
    tasks with no active copy come out +inf); ``comm_eps`` the per-message
    overhead.  ``record_trace`` / ``greedy_impl`` are accepted for
    signature uniformity with the rounds axis (see ``sweep``)."""
    from .scheduling import _resolve_greedy_impl
    _reject_single_round_trace(record_trace, "task_arrival_samples")
    _resolve_greedy_impl(greedy_impl)
    n = np.asarray(C).shape[0]
    spec = tau_spec("tau", C, messages=messages, loads=loads,
                    comm_eps=comm_eps)
    return _run([spec], model, n, trials=trials, seed=seed, chunk=chunk,
                ks=None, want_samples=True, devices=devices)[spec.name]


# ----------------------------- rounds axis -----------------------------------

def _build_rounds_fn(specs: Tuple[SchemeSpec, ...], process, n: int,
                     r_max: int, ks: int, rounds: int, beta: float,
                     gamma: float, censored: bool,
                     deadline: Optional[float] = None,
                     policy: str = "wait",
                     greedy_impl: Optional[str] = None):
    """Multi-round evaluator: (chunk, 2) per-trial keys + (chunk,) global
    trial ids -> {name: (rounds, chunk)} per-round completion times.

    Trial ids exist for trace-backed processes
    (``repro.core.trace.TraceProcess``): they tell each lane which trial
    of the recorded table it replays, so replay — like sampling — is
    invariant to how the trial axis is chunked.  Parametric processes are
    fully determined by their per-trial keys and ignore the ids.

    One ``lax.scan`` over rounds carries (a) the delay process state — the
    straggler persistence — and (b) the adaptive schemes' per-trial EMA of
    observed per-worker compute delays.  Every scheme scores the same delay
    realization each round (common random numbers), so per-round and
    cumulative scheme gaps are paired-sample estimates.

    With ``censored`` the adaptive feedback is restricted to what a real
    master sees: only messages that arrived before *that scheme's own* round
    completion are observed, each scheme carries its own estimate state, and
    a worker that delivered nothing keeps its previous estimate (new workers
    start at +inf, i.e. sorted slowest until they first deliver).  The
    uncensored path keeps the original idealized full-delay feedback,
    bit-identical to the pre-censoring engine.

    ``deadline`` caps every round (fault tolerance): the returned stream
    becomes ``(times, aux)`` with per-scheme degradation streams
    (``realized``, ``missed``, ``stale`` — each (rounds, chunk)):

    * ``wait``          — times unchanged (a round missing k arrivals
                          forever reports +inf); ``missed`` marks rounds
                          whose completion exceeded the deadline.
    * ``close_partial`` — the round closes at ``min(t_done, deadline)``
                          with however many distinct results arrived;
                          ``realized`` is that count (capped at k),
                          ``stale`` the per-round missing gradient mass
                          ``(k - realized) / k``.
    * ``reissue``       — like ``close_partial``, but undelivered tasks
                          accumulate in a per-trial backlog that adaptive
                          schemes re-gather first next round (the greedy
                          assignment's ``need`` priority); ``stale`` is
                          ``backlog / k`` (how much re-gathering is owed).

    With ``deadline=None`` the aux dict is empty and every number is
    bit-identical to the pre-deadline engine.
    """
    from . import scheduling                    # adaptive assignment

    static_specs = tuple(sp for sp in specs if sp.kind != "adaptive")
    ad_specs = tuple(sp for sp in specs if sp.kind == "adaptive")
    eval_fn = (_build_eval(static_specs, n, r_max, ks, deadline)
               if static_specs else None)
    # numpy scalars, NOT eager jnp arrays: this builder runs outside jit,
    # and concrete jax scalars closed over by the sharded rounds program
    # would be device-0-resident buffers; plain literals fold into the
    # traced program identically on every device and promote identically
    # in float32 arithmetic.
    DL = None if deadline is None else np.float32(deadline)
    reissue = deadline is not None and policy == "reissue"
    kf = np.float32(ks)
    nf = np.float32(n)

    def _policy_close(v, by, dv):
        """Apply the fallback policy to one scheme's raw completion ``v``
        (chunk,) given its arrival counts: returns (v_eff, realized,
        missed)."""
        if policy == "wait":
            return v, jnp.minimum(dv, kf), (~(v <= DL)).astype(jnp.float32)
        return (jnp.minimum(v, DL), jnp.minimum(by, kf),
                (by < kf).astype(jnp.float32))
    ad_mats = tuple(sp.matrix() for sp in ad_specs)
    # rebalance specs mask slots dynamically, so their plan must keep every
    # slot of the dense base (an identity plan — a static slot map would
    # bake the *initial* budget's message grouping into every round);
    # static ragged specs bake their masks in.
    ad_plans = tuple(task_gather_plan(sp.matrix(), n, r_max)
                     if sp.rebalance else _plan_of(sp, n, r_max)
                     for sp in ad_specs)
    ad_mmaps = tuple(None if sp.rebalance else _slot_map_of(sp)
                     for sp in ad_specs)
    # rebalance x message-budget composition: the closing-slot remap is a
    # runtime gather indexed by each row's realized load (see
    # ``_rebalance_remap``).
    ad_remap = tuple(_rebalance_remap(sp) for sp in ad_specs)
    # static per-row loads for ragged bases (rows carry their loads through
    # the re-permutation); None for dense bases (no masking needed).
    ad_lrow = tuple(None if sp.loads is None or sp.rebalance
                    else np.asarray(sp.loads, np.int64) for sp in ad_specs)
    # initial per-worker budgets for rebalance specs
    ad_l0 = tuple(np.asarray(sp.loads, np.int64) if sp.rebalance else None
                  for sp in ad_specs)

    def _assign_and_score(i, est, s, need=None):
        """Greedy row re-assignment (and, for rebalance specs, greedy load
        re-allocation) from ``est`` feedback, then this scheme's completion
        time on the permuted (and masked) slot grid.  Returns
        ``(w_of_row, loads_w, val, tau, tie_rows)`` with ``loads_w`` None
        for fixed-load specs and ``tie_rows`` the chunk's rows where the
        re-balance ran its descent (0 for fixed-load specs).  ``need``
        (reissue policy) prioritizes rows holding backlogged tasks in the
        greedy pick order."""
        sp, plan, Cb = ad_specs[i], ad_plans[i], ad_mats[i]
        # assignment uses feedback from *previous* rounds only.
        with jax.named_scope("engine.greedy"):
            w_of_row = scheduling.greedy_row_assignment_batch(
                Cb, est, gamma=gamma, need=need,
                impl=greedy_impl)               # (chunk, n)
        # row p's slots are executed by worker w_of_row[p]: permute the
        # worker axis, then the static gather plan applies.
        s2 = jnp.take_along_axis(s, w_of_row[..., None], axis=1)
        loads_w, tie_rows = None, 0
        if sp.rebalance:
            r_sp = Cb.shape[1]
            with jax.named_scope("engine.rebalance"):
                loads_w = scheduling.greedy_load_rebalance_batch(
                    est, ad_l0[i], r_max=r_sp, min_load=1)   # (chunk, n)
                # the same sort as the closed form's: XLA merges the two
                tied = scheduling._rebalance_fixed_point(est, ad_l0[i],
                                                         r_sp, 1)[1]
                tie_rows = jnp.where(tied.any(), est.shape[0], 0)
            # row p inherits its executor's load: mask the trailing slots
            # of the row-major grid to +inf before the static gather.
            l_row = jnp.take_along_axis(loads_w, w_of_row, axis=-1)
            s2 = jnp.where(jnp.arange(s2.shape[-1])[None, None, :]
                           < l_row[..., None], s2, INF)
            if ad_remap[i] is not None:
                # multi-message budget: slot j's result rides its message's
                # closing slot, whose position depends on the row's
                # realized load — gather the per-load remap row.
                mm = jnp.take(jnp.asarray(ad_remap[i]), l_row - 1, axis=0)
                s2 = jnp.take_along_axis(s2, mm, axis=-1)
        tau = task_arrival_times_gather(plan, s2)
        return w_of_row, loads_w, _kth_smallest(tau, ks), tau, tie_rows

    def _worker_arrivals(i, w_of_row, loads_w, s):
        """Worker-major per-message arrivals feeding the (censored)
        feedback: worker w's message arrivals are its own slots of ``s``
        whatever row it executes (the row permutation and its inverse
        cancel for the raw slots), masked to +inf beyond the worker's load
        this round.  A per-ROW message map travels with the assignment:
        worker w groups its slots by the layout of the row it executes."""
        Cb, mmap = ad_mats[i], ad_mmaps[i]
        r_sp = Cb.shape[1]
        s_w = s[..., :, :r_sp]
        if mmap is None:
            arr_w = s_w
        elif np.ndim(mmap) == 1:                      # row-invariant map
            arr_w = _apply_slot_map(s_w, mmap)
        else:
            # per-row map: permute the static (n, r) map to worker-major
            # (worker w uses the layout of row row_of_worker[w])
            row_of_worker = jnp.argsort(w_of_row, axis=-1)
            mm = jnp.take(jnp.asarray(mmap), row_of_worker, axis=0)
            arr_w = jnp.take_along_axis(s_w, mm, axis=-1)
        if loads_w is not None:                       # rebalance: dynamic
            if ad_remap[i] is not None:
                # each worker groups its own realized load into messages:
                # remap to closing-slot arrivals before masking.
                mm = jnp.take(jnp.asarray(ad_remap[i]), loads_w - 1, axis=0)
                arr_w = jnp.take_along_axis(arr_w, mm, axis=-1)
            act = jnp.arange(r_sp)[None, None, :] < loads_w[..., None]
            arr_w = jnp.where(act, arr_w, INF)
        elif ad_lrow[i] is not None:                  # static ragged rows
            row_of_worker = jnp.argsort(w_of_row, axis=-1)
            l_of_w = jnp.take(jnp.asarray(ad_lrow[i]), row_of_worker)
            act = jnp.arange(r_sp)[None, None, :] < l_of_w[..., None]
            arr_w = jnp.where(act, arr_w, INF)
        return arr_w

    def _eval_static(s):
        """Static-scheme raw stats + (with a deadline) arrival counts."""
        if eval_fn is None:
            return {}, {}
        if DL is None:
            return dict(eval_fn(s)), {}
        out, cnts = eval_fn(s)
        return dict(out), cnts

    def _degrade(nm, v, by, dv, backs, new_backs):
        """Policy application + degradation streams for one scheme.
        Returns (v_eff, aux | None); updates ``new_backs`` under reissue."""
        if DL is None:
            return v, None
        v_eff, realized, missed = _policy_close(v, by, dv)
        if reissue:
            nb = jnp.clip(backs[nm] + kf - jnp.minimum(by, kf), 0.0, nf)
            new_backs[nm] = nb
            stale = nb / kf
        else:
            stale = (kf - realized) / kf
        return v_eff, {"realized": realized, "missed": missed,
                       "stale": stale}

    def rounds_fn(keys: Array, tids: Array):
        chunk = keys.shape[0]
        # one subkey per (trial, round) + one for the process init, derived
        # from the per-trial key so everything stays chunk-invariant.
        allk = jax.vmap(lambda kk: jax.random.split(kk, rounds + 1))(keys)
        pstate = process.init_trials(allk[:, 0], tids, n)
        backs0 = ({sp.name: jnp.zeros((chunk,), jnp.float32)
                   for sp in specs} if reissue else {})
        needs0 = ({sp.name: jnp.zeros((chunk, n), jnp.float32)
                   for sp in ad_specs} if reissue else {})

        def _adaptive_round(i, est, s, needs, backs, new_backs, new_needs,
                            times, aux):
            """One adaptive scheme's round: assign (+ reissue priority),
            score, apply the deadline policy, update the reissue backlog /
            need.  Returns what the censored feedback update needs, and
            the re-balance's descent rows."""
            sp = ad_specs[i]
            need = needs.get(sp.name) if reissue else None
            w_of_row, loads_w, val, tau, tie_rows = _assign_and_score(
                i, est, s, need)
            v = val[..., 0]
            if DL is None:
                by = dv = None
            else:
                by = (tau <= DL).sum(-1).astype(jnp.float32)
                dv = jnp.isfinite(tau).sum(-1).astype(jnp.float32)
            v_eff, a = _degrade(sp.name, v, by, dv, backs, new_backs)
            if a is not None:
                aux[sp.name] = a
            if reissue:
                # undelivered tasks become next round's re-gather priority
                # (only while a backlog is actually owed)
                delivered = (tau <= v_eff[..., None]) & jnp.isfinite(tau)
                owed = (new_backs[sp.name] > 0)[..., None]
                new_needs[sp.name] = (~delivered & owed).astype(jnp.float32)
            times[sp.name] = v_eff
            return w_of_row, loads_w, v_eff, tie_rows

        # NB: the round index rides the scan xs (an ``arange``) instead of
        # an integer carry — numerically identical, and immune to a
        # multi-device host-mesh miscompilation once seen under
        # ``shard_map`` (JAX 0.4) where XLA aliased constant-initialized
        # scalar carries across co-resident shards, so ``t == 0``
        # misfired on every device but the first.
        if censored:
            def body(carry, xs):
                kr, _ = xs
                pstate, ests, needs, backs = carry
                pstate, T1, T2 = process.step(pstate, kr, n, r_max)
                s = jnp.cumsum(T1, axis=-1) + T2    # eq. (1), per round
                out, cnts = _eval_static(s)
                times, aux = {}, {}
                new_backs, new_needs = {}, {}
                for sp in static_specs:
                    by, dv = cnts.get(sp.name, (None, None))
                    v_eff, a = _degrade(sp.name, out[sp.name][..., 0],
                                        by, dv, backs, new_backs)
                    times[sp.name] = v_eff
                    if a is not None:
                        aux[sp.name] = a
                new_e, ties = [], 0
                for i, (sp, Cb, est) in enumerate(zip(ad_specs, ad_mats,
                                                      ests)):
                    w_of_row, loads_w, v_eff, tie_rows = _adaptive_round(
                        i, est, s, needs, backs, new_backs, new_needs,
                        times, aux)
                    ties = ties + tie_rows
                    r_sp = Cb.shape[1]
                    # shared censored update: only messages that beat this
                    # scheme's own round close are observed (the deadline
                    # policies censor at the effective close).
                    arr_w = _worker_arrivals(i, w_of_row, loads_w, s)
                    new_e.append(scheduling.censored_feedback_update(
                        est, T1[..., :r_sp], arr_w, v_eff, beta=beta))
                return ((pstate, tuple(new_e), new_needs, new_backs),
                        (times, aux, jnp.int32(ties)))

            init = (pstate,
                    tuple(jnp.full((chunk, n), INF, jnp.float32)
                          for _ in ad_specs), needs0, backs0)
        else:
            def body(carry, xs):
                kr, t = xs
                pstate, est, needs, backs = carry
                pstate, T1, T2 = process.step(pstate, kr, n, r_max)
                s = jnp.cumsum(T1, axis=-1) + T2    # eq. (1), per round
                out, cnts = _eval_static(s)
                times, aux = {}, {}
                new_backs, new_needs = {}, {}
                for sp in static_specs:
                    by, dv = cnts.get(sp.name, (None, None))
                    v_eff, a = _degrade(sp.name, out[sp.name][..., 0],
                                        by, dv, backs, new_backs)
                    times[sp.name] = v_eff
                    if a is not None:
                        aux[sp.name] = a
                ties = 0
                for i in range(len(ad_specs)):
                    ties = ties + _adaptive_round(
                        i, est, s, needs, backs, new_backs, new_needs,
                        times, aux)[-1]
                obs = T1.mean(axis=-1)              # per-worker compute time
                # +inf-safe: a fault-censored worker's +inf observation
                # keeps the previous estimate (EMAing it would pin est at
                # +inf forever); bit-identical when all delays are finite.
                fin = jnp.isfinite(obs)
                upd = jnp.where(t == 0, obs, beta * est + (1.0 - beta) * obs)
                est = jnp.where(fin, upd, est)
                return ((pstate, est, new_needs, new_backs),
                        (times, aux, jnp.int32(ties)))

            init = (pstate, jnp.ones((chunk, n), jnp.float32),
                    needs0, backs0)

        with jax.named_scope("engine.rounds_scan"):
            _, ys = jax.lax.scan(body, init,
                                 (jnp.swapaxes(allk[:, 1:], 0, 1),
                                  jnp.arange(rounds, dtype=jnp.int32)))
        # ({name: (rounds, chunk)}, {name: aux dicts}, (rounds,) the
        # chunk's (trial, round) rows where a re-balance ran its descent)
        return ys

    return rounds_fn


_ROUNDS_CACHE = _LRUCache()


def _get_rounds_exec(specs: Tuple[SchemeSpec, ...], process, n: int,
                     r_max: int, ks: int, rounds: int, beta: float,
                     gamma: float, censored: bool,
                     deadline: Optional[float] = None, policy: str = "wait",
                     devices: tuple = (), greedy_impl: Optional[str] = None):
    from .trace import TraceProcess
    cache_key = None
    if isinstance(process, TraceProcess):
        # uncached: the compiled program closes over the full delay tables
        # (hundreds of MB for big recordings) and traces are one-shot —
        # caching would pin every trace ever swept for the process's life.
        pass
    else:
        try:
            cache_key = (specs, process, n, r_max, ks, rounds, beta, gamma,
                         censored, deadline, policy, devices, greedy_impl)
            hit = _ROUNDS_CACHE.get(cache_key)
            if hit is not None:
                return hit
        except TypeError:           # unhashable custom process: uncached
            cache_key = None

    rounds_fn = _build_rounds_fn(specs, process, n, r_max, ks, rounds,
                                 beta, gamma, censored, deadline, policy,
                                 greedy_impl)
    has_dl = deadline is not None

    def _chunk_aux(aux, vd):
        """One chunk's degradation partials: valid-masked sums over the
        trial axis plus the realized-k histogram (one_hot over 0..k)."""
        ok = vd[None, :]                              # (1, chunk) bool
        okf = vd.astype(jnp.float32)[None, :, None]
        out = {}
        for nm, a in aux.items():
            hist = (jax.nn.one_hot(a["realized"].astype(jnp.int32), ks + 1)
                    * okf).sum(axis=1)
            out[nm] = {
                "realized": jnp.where(ok, a["realized"], 0.0).sum(axis=1),
                "missed": jnp.where(ok, a["missed"], 0.0).sum(axis=1),
                "stale": jnp.where(ok, a["stale"], 0.0).sum(axis=1),
                "khist": hist,
            }
        return out

    def sums_scan(base_key, starts, offs, limit):
        _count_trace()

        def body(carry, start):
            tids_raw = start + offs
            tc = jnp.minimum(tids_raw, limit - 1)
            ys, aux, ties = rounds_fn(_fold_keys(base_key, tc), tc)
            vd = tids_raw < limit
            ok = vd[None, :]
            cum = {k2: jnp.cumsum(v, axis=0) for k2, v in ys.items()}
            s0 = {k2: jnp.where(ok, ys[k2], 0.0).sum(axis=1) for k2 in ys}
            s1 = {k2: jnp.where(ok, jnp.square(ys[k2]), 0.0).sum(axis=1)
                  for k2 in ys}
            c0 = {k2: jnp.where(ok, cum[k2], 0.0).sum(axis=1) for k2 in cum}
            c1 = {k2: jnp.where(ok, jnp.square(cum[k2]), 0.0).sum(axis=1)
                  for k2 in cum}
            ac = _chunk_aux(aux, vd) if has_dl else {}
            return carry, (s0, s1, c0, c1, ac, ties.sum())

        _, parts = jax.lax.scan(body, None, starts)
        return parts   # 4 x {name: (nc, rounds)}, degradation, (nc,) ties

    def samples_scan(base_key, starts, offs, limit):
        _count_trace()

        def body(carry, start):
            tc = jnp.minimum(start + offs, limit - 1)
            # times and ties only (aux is DCE'd)
            ys, _, ties = rounds_fn(_fold_keys(base_key, tc), tc)
            return carry, (ys, ties.sum())

        _, out = jax.lax.scan(body, None, starts)
        return out            # ({name: (nc, R, chunk)}, (nc,) ties)

    if len(devices) > 1:
        # shard_trials returns a fully-jitted callable; no outer jit.
        exec_ = (shard_trials(sums_scan, devices, replicated=(0, 2, 3)),
                 shard_trials(samples_scan, devices, replicated=(0, 2, 3)))
    else:
        exec_ = (jax.jit(sums_scan), jax.jit(samples_scan))
    exec_ = (_timed_first(exec_[0], _ROUNDS_CACHE),
             _timed_first(exec_[1], _ROUNDS_CACHE))
    if cache_key is not None:
        _ROUNDS_CACHE.put(cache_key, exec_)
    return exec_


def _capture_rounds_fn(process, n: int, r_max: int, rounds: int):
    """The recording pass: scan the process alone (same per-trial key
    derivation as ``_build_rounds_fn``), streaming out the realized delay
    tensors — (chunk, 2) keys + (chunk,) trial ids ->
    ``(T1, T2)`` of shape (rounds, chunk, n, r_max) each."""
    def capture_fn(keys: Array, tids: Array):
        allk = jax.vmap(lambda kk: jax.random.split(kk, rounds + 1))(keys)
        pstate = process.init_trials(allk[:, 0], tids, n)

        def body(pstate, kr):
            pstate, T1, T2 = process.step(pstate, kr, n, r_max)
            return pstate, (T1, T2)

        _, recs = jax.lax.scan(body, pstate,
                               jnp.swapaxes(allk[:, 1:], 0, 1))
        return recs

    return capture_fn


def _record_trace(process, n, r_max, *, rounds, trials, seed, chunk,
                  meta: dict):
    """Capture the delay tables a rounds run over ``process`` realizes,
    as a ``repro.core.trace.DelayTrace``.

    This is the first pass of ``record_trace=True``: the per-trial key
    derivation is identical to the evaluation scan, so the captured
    tables are exactly the delays any sweep over the same
    (process, seed, trials) draws.  The evaluation pass then *replays*
    these materialized tables (``TraceProcess``), which makes the
    reported statistics bit-exactly reproducible from the returned trace
    — XLA is free to fuse a parametric process's arithmetic into eq. (1)
    with fused-multiply-adds, so values consumed in a fused sampling run
    can differ from any materialized table by ulps; evaluating through
    the replay path removes that divergence by construction.
    """
    from .trace import DelayTrace
    capture = jax.jit(_capture_rounds_fn(process, n, r_max, rounds))
    keys = trial_keys(seed, trials)
    tids = jnp.arange(trials, dtype=jnp.int32)
    parts1, parts2 = [], []
    for lo in range(0, trials, chunk):
        T1c, T2c = capture(keys[lo:lo + chunk], tids[lo:lo + chunk])
        parts1.append(np.asarray(T1c))
        parts2.append(np.asarray(T2c))
    T1 = np.concatenate(parts1, axis=1) if len(parts1) > 1 else parts1[0]
    T2 = np.concatenate(parts2, axis=1) if len(parts2) > 1 else parts2[0]
    return DelayTrace(T1, T2, meta=meta)


def _check_rounds_args(specs, n, ks, rounds):
    specs = _check_specs(specs, n)
    for sp in specs:
        if sp.kind == "tau":
            raise ValueError(f"{sp.name}: tau specs are single-round only")
    if not 1 <= ks <= n:
        raise ValueError(f"need 1 <= k <= n={n}, got k={ks}")
    for sp in specs:
        if (sp.kind in ("to", "adaptive") and not sp.rebalance
                and _covered_tasks(sp) < ks):
            raise ValueError(
                f"{sp.name}: ragged schedule covers only "
                f"{_covered_tasks(sp)} distinct tasks < k={ks}; the "
                f"completion time would be infinite")
    if rounds < 1:
        raise ValueError(f"need rounds >= 1, got {rounds}")
    return specs


_POLICIES = DEADLINE_POLICIES        # canonical tuple lives in repro.core.spec


def _run_rounds(specs, process, n, *, rounds: int, k: int, trials: int,
                seed: int, chunk: Optional[int], beta: float, gamma: float,
                censored: bool, want_samples: bool, record: bool = False,
                deadline: Optional[float] = None,
                deadline_policy: str = "wait", devices=None,
                greedy_impl: Optional[str] = None):
    from .cluster import as_process
    from .scheduling import _resolve_greedy_impl
    process = as_process(process)
    process.check_rounds(rounds)
    specs = _check_rounds_args(specs, n, k, rounds)
    deadline = validate_deadline(deadline, deadline_policy)
    _resolve_greedy_impl(greedy_impl)       # validate early (clear error)
    r_max = max(sp.load for sp in specs)
    chunk = _normalize_chunk(trials, chunk)

    if record:
        # two-pass recording: capture the realized delay tables first,
        # then evaluate by REPLAYING them — the reported statistics are
        # then bit-exactly reproducible from the returned trace (see
        # ``_record_trace``).
        from .trace import TraceProcess
        trace = _record_trace(
            process, n, r_max, rounds=rounds, trials=trials, seed=seed,
            chunk=chunk,
            meta={"source": "sweep_rounds", "seed": int(seed), "k": int(k),
                  "process": type(process).__name__,
                  "schemes": [sp.name for sp in specs]})
        out = _run_rounds(specs, TraceProcess(trace), n, rounds=rounds,
                          k=k, trials=trials, seed=seed, chunk=chunk,
                          beta=beta, gamma=gamma, censored=censored,
                          want_samples=want_samples, deadline=deadline,
                          deadline_policy=deadline_policy, devices=devices,
                          greedy_impl=greedy_impl)
        return out[:-1] + (trace,)

    with obs.span("engine.dispatch"):
        devs, nc_pad, padded = _shard_layout(trials, chunk, devices)
        jsums, jsamples = _get_rounds_exec(
            specs, process, n, r_max, k, rounds, beta, gamma, censored,
            deadline, deadline_policy, devs, greedy_impl)
        # (trial, round) rows the greedy and the re-balance solve,
        # padding included
        obs.count("engine.greedy_rows", padded * rounds * sum(
            sp.kind == "adaptive" for sp in specs))
        obs.count("engine.rebalance_rows", padded * rounds * sum(
            sp.rebalance for sp in specs))

        # the scans derive per-trial keys AND trial ids device-side from
        # the (base key, per-chunk start) coordinates: padded lanes replay
        # a valid (clamped) trial id — deriving the last real trial's key,
        # exactly the ``_padded_keys`` reference twin — and are masked out
        # of every statistic below, so trace replay stays invariant to
        # chunking AND sharding without a host key table.
        base_key = jax.random.PRNGKey(seed)
        starts, offs, limit = _scan_coords(trials, chunk, nc_pad)
        out = (jsamples if want_samples else jsums)(base_key, starts, offs,
                                                    limit)
    with obs.span("engine.wait"):
        jax.block_until_ready(out)

    *out, ties = out
    with obs.span("engine.combine"):
        if obs.enabled():
            obs.count("engine.rebalance_tie_rows", int(np.sum(ties)))
        if want_samples:
            return ({nm: jnp.moveaxis(v, 1, -1).reshape(padded,
                                                         rounds)[:trials]
                     for nm, v in out[0].items()}, None)  # ->(trials, R)
        return _rounds_moments(out, trials, deadline)


def _rounds_moments(parts, trials: int, deadline: Optional[float]):
    """Per-round and wall-clock means and standard errors (and the
    degradation streams) from the rounds scan's per-chunk partials."""
    s0, s1, c0, c1, ac = parts

    def moments(parts0, parts1):
        # per-chunk float32 partials -> float64 in global chunk order: the
        # same reduction whatever the device count (bit-exact sharding).
        mu = np.asarray(parts0, np.float64).sum(axis=0) / trials
        sq = np.asarray(parts1, np.float64).sum(axis=0)
        var = np.maximum(sq / trials - mu * mu, 0.0)
        return mu, np.sqrt(var / trials)

    per_round, stderr, wallclock, wc_stderr = {}, {}, {}, {}
    for nm in s0:
        per_round[nm], stderr[nm] = moments(s0[nm], s1[nm])
        wallclock[nm], wc_stderr[nm] = moments(c0[nm], c1[nm])
    degr = None
    if deadline is not None:
        degr = {nm: {"realized_k": np.asarray(d["realized"],
                                              np.float64).sum(0) / trials,
                     "missed": np.asarray(d["missed"],
                                          np.float64).sum(0) / trials,
                     "stale": np.asarray(d["stale"],
                                         np.float64).sum(0) / trials,
                     "khist": np.asarray(d["khist"],
                                         np.float64).sum(0) / trials}
                for nm, d in ac.items()}
    return per_round, stderr, wallclock, wc_stderr, degr, None


@dataclasses.dataclass(frozen=True)
class RoundsResult:
    """Wall-clock trajectories from a multi-round sweep.

    ``per_round[name]``  — (rounds,) mean completion time of each round;
    ``wallclock[name]``  — (rounds,) mean *cumulative* wall-clock after each
                           round (the x-axis of a loss-vs-time curve);
    ``stderr`` / ``wallclock_stderr`` — matching MC standard errors;
    ``trace``            — the realized delay tables of the whole sweep
                           (a ``repro.core.trace.DelayTrace``) when run
                           with ``record_trace=True``, else None;
    ``degradation``      — per-scheme graceful-degradation streams when run
                           with a ``deadline``: ``realized_k`` (rounds,)
                           mean distinct results credited per round,
                           ``missed`` (rounds,) fraction of trials whose
                           round missed the deadline, ``stale`` (rounds,)
                           mean missing-gradient fraction (reissue: owed
                           backlog / k), ``khist`` (rounds, k+1) the
                           realized-k distribution.  None without a
                           deadline.
    """
    per_round: Dict[str, np.ndarray]
    stderr: Dict[str, np.ndarray]
    wallclock: Dict[str, np.ndarray]
    wallclock_stderr: Dict[str, np.ndarray]
    trials: int
    rounds: int
    n: int
    k: int
    trace: Optional[object] = None
    deadline: Optional[float] = None
    deadline_policy: str = "wait"
    degradation: Optional[Dict[str, Dict[str, np.ndarray]]] = None

    def _get(self, d: Dict[str, np.ndarray], name: str) -> np.ndarray:
        if name not in d:
            raise ValueError(f"unknown scheme {name!r}; have "
                             f"{sorted(d)}")
        return d[name]

    def mean_round(self, name: str) -> float:
        """Mean completion time per round, averaged over the run."""
        return float(self._get(self.per_round, name).mean())

    def total(self, name: str) -> float:
        """Mean wall-clock of the whole R-round run."""
        return float(self._get(self.wallclock, name)[-1])

    def _degr(self, name: str, key: str) -> np.ndarray:
        if self.degradation is None:
            raise ValueError("no degradation metrics: run sweep_rounds "
                             "with a deadline")
        return self._get(self.degradation, name)[key]

    def realized_k(self, name: str) -> np.ndarray:
        """(rounds,) mean distinct results credited per round (<= k)."""
        return self._degr(name, "realized_k")

    def missed_fraction(self, name: str) -> np.ndarray:
        """(rounds,) fraction of trials whose round missed the deadline."""
        return self._degr(name, "missed")

    def stale_fraction(self, name: str) -> np.ndarray:
        """(rounds,) mean missing-gradient fraction per round."""
        return self._degr(name, "stale")

    def khist(self, name: str) -> np.ndarray:
        """(rounds, k+1) realized-k distribution (rows sum to 1)."""
        return self._degr(name, "khist")


@obs.span("engine.rounds")
def sweep_rounds(specs: Sequence[SchemeSpec], process, n: int, *,
                 rounds: int, k: int, trials: int = 20000, seed: int = 0,
                 chunk: Optional[int] = None, feedback_beta: float = 0.7,
                 coverage_gamma: float = 0.5,
                 censored_feedback: bool = False,
                 record_trace: bool = False,
                 deadline: Optional[float] = None,
                 deadline_policy: str = "wait", devices=None,
                 greedy_impl: Optional[str] = None) -> RoundsResult:
    """Evaluate every scheme over ``rounds`` consecutive rounds of ONE
    shared ``DelayProcess`` realization per trial.

    Parameters
    ----------
    specs:   schemes to evaluate; ``adaptive_spec`` entries re-assign their
             base matrix's rows each round from delay feedback (and, with
             ``rebalance=True``, re-allocate whole slots between workers
             under the fixed total budget — Egger-style load adaptation).
    process: a ``DelayProcess`` (or a stateless ``DelayModel``, coerced to
             the zero-correlation ``IIDProcess``).
    rounds:  number of consecutive SGD rounds scanned per trial.
    k:       computation target (single k; the rounds axis replaces the
             all-k axis of single-round sweeps).
    trials/seed/chunk: as in ``sweep`` — per-trial subkeys, chunk-invariant
             streaming with O(chunk * n * r_max) memory.
    feedback_beta:  EMA weight on past feedback in adaptive schemes.
    coverage_gamma: per-slot coverage discount of the greedy assignment.
    censored_feedback: restrict adaptive feedback to messages that arrived
             before the scheme's own round completion (what a real master
             observes) instead of the idealized full-delay feedback.
    record_trace: also capture the realized per-(round, trial, worker,
             slot) delay tables — the result's ``trace`` field becomes a
             ``repro.core.trace.DelayTrace``.  Recording is two-pass: the
             process is scanned once to materialize the tables, and the
             reported statistics are computed by *replaying* them, so a
             later ``TraceProcess`` replay reproduces this result
             bit-exactly (a fused sampling run may differ by float32 ulps
             — XLA contracts a process's arithmetic into eq. (1) with
             FMAs).  Memory: O(rounds * trials * n * r_max) floats x2.
    deadline: cap every round at this wall-clock budget (fault tolerance —
             with fault-injecting processes a round may otherwise never
             reach k results).  Enables the ``degradation`` metrics.
    deadline_policy: what happens at the deadline — ``"wait"`` (report the
             true completion, just flag the miss), ``"close_partial"``
             (close the round with whatever arrived), or ``"reissue"``
             (close partial + adaptive schemes re-gather the undelivered
             tasks first next round).
    devices: shard the trial axis across devices (as in ``sweep``) —
             bit-exact vs. single-device for the same (trials, seed,
             chunk); pass ``chunk <= trials // len(devices)`` to engage
             every device.
    greedy_impl: how adaptive specs run the greedy pick loop —
             ``None``/``"auto"`` (Pallas kernel on compiled backends, jnp
             scan on CPU), ``"kernel"``, or ``"scan"``.
    """
    per_round, stderr, wallclock, wc_stderr, degr, trace = _run_rounds(
        specs, process, n, rounds=rounds, k=k, trials=trials, seed=seed,
        chunk=chunk, beta=feedback_beta, gamma=coverage_gamma,
        censored=censored_feedback, want_samples=False,
        record=record_trace, deadline=deadline,
        deadline_policy=deadline_policy, devices=devices,
        greedy_impl=greedy_impl)
    return RoundsResult(per_round=per_round, stderr=stderr,
                        wallclock=wallclock, wallclock_stderr=wc_stderr,
                        trials=trials, rounds=rounds, n=n, k=k, trace=trace,
                        deadline=deadline, deadline_policy=deadline_policy,
                        degradation=degr)


def trajectory_samples(spec: SchemeSpec, process, n: int, *, rounds: int,
                       k: int, trials: int = 10000, seed: int = 0,
                       chunk: Optional[int] = None,
                       feedback_beta: float = 0.7,
                       coverage_gamma: float = 0.5,
                       censored_feedback: bool = False,
                       record_trace: bool = False,
                       deadline: Optional[float] = None,
                       deadline_policy: str = "wait", devices=None,
                       greedy_impl: Optional[str] = None):
    """Per-trial completion-time trajectories for one scheme: shape
    ``(trials, rounds)``; ``jnp.cumsum(..., axis=1)`` gives per-trial
    wall-clock curves.  With ``record_trace=True`` returns
    ``(trajectories, DelayTrace)`` — the realized delay tables alongside
    the samples.  With a ``deadline`` the trajectories are the *effective*
    round closes under ``deadline_policy`` (capped at the deadline for
    ``close_partial``/``reissue``)."""
    samples, trace = _run_rounds([spec], process, n, rounds=rounds, k=k,
                                 trials=trials, seed=seed, chunk=chunk,
                                 beta=feedback_beta, gamma=coverage_gamma,
                                 censored=censored_feedback,
                                 want_samples=True, record=record_trace,
                                 deadline=deadline,
                                 deadline_policy=deadline_policy,
                                 devices=devices, greedy_impl=greedy_impl)
    if record_trace:
        return samples[spec.name], trace
    return samples[spec.name]
