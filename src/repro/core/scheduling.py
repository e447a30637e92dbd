"""Task-ordering (TO) matrices — the paper's central object.

A TO matrix ``C`` is an ``(n, r)`` integer matrix. Row ``i`` lists the task
indices worker ``i`` executes, in order: worker ``i`` first computes
``h(X[C[i, 0]])``, then ``h(X[C[i, 1]])``, ... (paper Sec. II). Tasks are
0-indexed here (the paper is 1-indexed).

Ragged per-worker loads
-----------------------
The paper fixes one computation load ``r`` for every worker, but real
clusters are heterogeneous (paper Sec. VI EC2 measurements), and *reducing*
a slow worker's load beats merely re-ordering its tasks (Egger et al.,
arXiv:2304.08589).  Every construction therefore accepts a per-worker load
vector ``loads`` (``loads[i] <= r_max``): the result is still a rectangular
``(n, r_max)`` grid, with row ``i``'s trailing ``r_max - loads[i]`` slots
holding the sentinel ``MASKED`` (-1).  A uniform ``loads`` is exactly the
dense matrix.  ``loads_of_matrix`` recovers the load vector from the
sentinels; ``validate_to_matrix`` checks coverage/distinctness on the
active prefix of each row.  ``greedy_load_rebalance`` reallocates whole
slots between workers from delay feedback under a fixed total budget
(slow workers shed slots to fast ones, makespan-greedy).

Implemented schedules:
  * Cyclic scheduling   (CS, paper eq. 21):  C(i,j) = g(i + j)
  * Staircase scheduling (SS, paper eq. 29): C(i,j) = g(i + (-1)^i * j)
  * Random assignment   (RA, [18]):          each row an independent random
    permutation of [n] (requires r == n)
  * round-robin block / custom matrices via validation helpers.

Adaptive row assignment
-----------------------
The static schedules fix which worker executes which row forever.  Under
heterogeneous, *persistent* stragglers (see ``repro.core.cluster``) that
leaves completion time hostage to the luck of which rows the slow machines
drew: the tasks whose early copies all sit at stragglers arrive last.
``greedy_row_assignment`` re-permutes the rows of a base TO matrix each
round from observed per-worker delay feedback — fastest workers pick first,
and each picks the row whose leading slots cover the currently
least-covered tasks (coverage discounted by slot position and weighted by
the picker's speed).  ``AdaptiveScheduler`` wraps this with an EMA of the
feedback for use in training loops; the batched JAX variant
(``greedy_row_assignment_batch``) runs per-trial inside the fused rounds
engine (``repro.core.montecarlo.sweep_rounds``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MASKED",
    "cyclic_to_matrix",
    "staircase_to_matrix",
    "random_assignment_to_matrix",
    "block_to_matrix",
    "validate_to_matrix",
    "loads_of_matrix",
    "mask_matrix_loads",
    "to_matrix",
    "SCHEDULES",
    "Schedule",
    "greedy_row_assignment",
    "greedy_row_assignment_batch",
    "greedy_load_rebalance",
    "greedy_load_rebalance_batch",
    "censored_feedback_update",
    "AdaptiveScheduler",
]

MASKED = -1      # sentinel task index for the inactive trailing slots of a
                 # ragged row (worker load < grid width)


def _g(m: np.ndarray, n: int) -> np.ndarray:
    """Paper's wrap-around map g (eq. 22), 0-indexed: fold into [0, n)."""
    return np.mod(m, n)


def _check_loads(n: int, loads, r: int | None) -> tuple[np.ndarray, int]:
    """Validate a per-worker load vector against ``n`` workers and an
    optional grid width ``r`` (defaults to ``max(loads)``).  Returns
    ``(loads, r_max)``."""
    lv = np.asarray(loads, np.int64)
    if lv.shape != (n,):
        raise ValueError(f"loads must have shape ({n},), got {lv.shape}")
    if lv.min() < 1:
        raise ValueError(f"every worker needs load >= 1, got min {lv.min()}")
    r_max = int(lv.max()) if r is None else int(r)
    if lv.max() > r_max:
        raise ValueError(f"max load {lv.max()} exceeds grid width r={r_max}")
    if not 1 <= r_max <= n:
        raise ValueError(f"need 1 <= r <= n, got r={r_max}, n={n}")
    return lv, r_max


def mask_matrix_loads(C: np.ndarray, loads) -> np.ndarray:
    """Apply a load vector to a dense TO matrix: slots ``j >= loads[i]`` of
    row ``i`` are replaced with the ``MASKED`` sentinel."""
    C = np.asarray(C).astype(np.int64).copy()
    lv, _ = _check_loads(C.shape[0], loads, C.shape[1])
    C[np.arange(C.shape[1])[None, :] >= lv[:, None]] = MASKED
    return C


def cyclic_to_matrix(n: int, r: int | None = None, *,
                     loads=None) -> np.ndarray:
    """CS schedule (eq. 21): every worker walks the ring in the same
    direction, offset by its index, so each task has the same execution
    *position* at every worker that holds it.  With ``loads``, row ``i``
    keeps only its first ``loads[i]`` slots (trailing slots ``MASKED``);
    the slot-0 diagonal ``C[i, 0] = i`` keeps every task covered for any
    load vector."""
    if loads is not None:
        _, r = _check_loads(n, loads, r)
    elif r is None:
        raise ValueError("need a load r (or a loads vector)")
    if not (1 <= r <= n):
        raise ValueError(f"need 1 <= r <= n, got r={r}, n={n}")
    i = np.arange(n)[:, None]
    j = np.arange(r)[None, :]
    C = _g(i + j, n).astype(np.int64)
    return C if loads is None else mask_matrix_loads(C, loads)


def staircase_to_matrix(n: int, r: int | None = None, *,
                        loads=None) -> np.ndarray:
    """SS schedule (eq. 29): even-indexed workers walk the ring ascending,
    odd-indexed workers descending (0-indexed parity matches the paper's
    1-indexed convention: paper worker 1 ≙ row 0 ascends).  ``loads`` masks
    each row's trailing slots as in ``cyclic_to_matrix``; the slot-0
    diagonal again guarantees coverage."""
    if loads is not None:
        _, r = _check_loads(n, loads, r)
    elif r is None:
        raise ValueError("need a load r (or a loads vector)")
    if not (1 <= r <= n):
        raise ValueError(f"need 1 <= r <= n, got r={r}, n={n}")
    i = np.arange(n)[:, None]
    j = np.arange(r)[None, :]
    sign = np.where(i % 2 == 0, 1, -1)
    C = _g(i + sign * j, n).astype(np.int64)
    return C if loads is None else mask_matrix_loads(C, loads)


def random_assignment_to_matrix(n: int, r: int | None = None, *,
                                rng: np.random.Generator | None = None,
                                seed: int | None = 0,
                                loads=None) -> np.ndarray:
    """RA scheme [18]: r = n (full dataset at each worker); each row is an
    independent uniformly random permutation of [n].  With ``loads``, row
    ``i`` starts at its own task ``i`` (restoring the coverage guarantee a
    truncated random permutation would lose) followed by a random
    permutation of the rest, truncated to ``loads[i]`` slots."""
    if loads is not None:
        lv, r_max = _check_loads(n, loads, r if r is not None else n)
        if rng is None:
            rng = np.random.default_rng(seed)
        C = np.full((n, r_max), MASKED, np.int64)
        for i in range(n):
            rest = rng.permutation(np.delete(np.arange(n), i))
            row = np.concatenate([[i], rest])
            C[i, :lv[i]] = row[:lv[i]]
        return C
    if r is not None and r != n:
        raise ValueError(f"RA requires r == n (got r={r}, n={n})")
    if rng is None:
        rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(n) for _ in range(n)]).astype(np.int64)


def block_to_matrix(n: int, r: int | None = None, *,
                    loads=None) -> np.ndarray:
    """Naive blocked redundancy baseline (not in the paper; useful ablation):
    worker i computes tasks {i, i+1, ..., i+r-1} like CS but all workers
    start from the *lowest* index of their block — i.e. identical to CS.
    Differs for the ablation where workers share a start: C(i,j) = g(⌊i/r⌋*r + j).
    ``loads`` masks trailing slots (note: unlike CS/SS, blocked rows have no
    slot-0 diagonal, so ragged blocks may leave tasks uncovered).
    """
    if loads is not None:
        _, r = _check_loads(n, loads, r)
    elif r is None:
        raise ValueError("need a load r (or a loads vector)")
    if not (1 <= r <= n):
        raise ValueError(f"need 1 <= r <= n, got r={r}, n={n}")
    i = np.arange(n)[:, None]
    j = np.arange(r)[None, :]
    C = _g((i // max(r, 1)) * r + j, n).astype(np.int64)
    return C if loads is None else mask_matrix_loads(C, loads)


def loads_of_matrix(C: np.ndarray) -> np.ndarray:
    """Per-worker load vector of a (possibly ragged) TO matrix: the number
    of active (non-``MASKED``) leading slots of each row.  Raises if a
    ``MASKED`` sentinel appears before an active slot (masks must be a
    trailing suffix) or a row is fully masked."""
    C = np.asarray(C)
    if C.ndim != 2:
        raise ValueError(f"TO matrix must be 2-D, got shape {C.shape}")
    active = C != MASKED
    loads = active.sum(axis=1).astype(np.int64)
    if loads.min() < 1:
        raise ValueError(f"row {int(loads.argmin())} has no active slots")
    # masks must be contiguous and trailing: row i active exactly at j < l_i
    expect = np.arange(C.shape[1])[None, :] < loads[:, None]
    if not np.array_equal(active, expect):
        bad = int(np.nonzero((active != expect).any(axis=1))[0][0])
        raise ValueError(f"row {bad} has a MASKED sentinel before an active "
                         f"slot; masks must be a trailing suffix: {C[bad]}")
    return loads


def validate_to_matrix(C: np.ndarray, n: int | None = None,
                       require_distinct: bool = True,
                       loads=None) -> None:
    """Check C is a valid TO matrix: shape (n, r), active entries in
    [0, n), optionally distinct within each row's active prefix (any
    optimal C has distinct rows, paper Sec. II).  Rows may be ragged:
    trailing slots holding the ``MASKED`` sentinel are inactive; ``loads``
    (optional) cross-checks the per-row active counts."""
    C = np.asarray(C)
    if C.ndim != 2:
        raise ValueError(f"TO matrix must be 2-D, got shape {C.shape}")
    n_ = C.shape[0] if n is None else n
    if n is not None and C.shape[0] != n:
        raise ValueError(f"TO matrix has {C.shape[0]} rows, expected n={n}")
    if C.shape[1] > n_:
        raise ValueError(f"computation load r={C.shape[1]} exceeds n={n_}")
    lv = loads_of_matrix(C)                # also checks trailing-mask shape
    if loads is not None:
        want, _ = _check_loads(C.shape[0], loads, C.shape[1])
        if not np.array_equal(lv, want):
            raise ValueError(f"matrix loads {lv.tolist()} do not match the "
                             f"given loads {want.tolist()}")
    act = C[C != MASKED]
    if act.min() < 0 or act.max() >= n_:
        raise ValueError(f"task indices must lie in [0, {n_}), got "
                         f"[{act.min()}, {act.max()}]")
    if require_distinct:
        for i, row in enumerate(C):
            row = row[:lv[i]]
            if len(set(row.tolist())) != len(row):
                raise ValueError(f"row {i} has repeated tasks: {row}")


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A named TO-matrix construction."""
    name: str
    build: Callable[..., np.ndarray]

    def __call__(self, n: int, r: int | None = None, **kw) -> np.ndarray:
        # ``r`` is passed through for every schedule — RA's builder rejects
        # r != n rather than silently ignoring the requested load.
        C = self.build(n, r, **kw)
        validate_to_matrix(C, n, loads=kw.get("loads"))
        return C


SCHEDULES: dict[str, Schedule] = {
    "cs": Schedule("cs", cyclic_to_matrix),
    "ss": Schedule("ss", staircase_to_matrix),
    "ra": Schedule("ra", random_assignment_to_matrix),
    "block": Schedule("block", block_to_matrix),
}


def to_matrix(name: str, n: int, r: int | None = None, **kw) -> np.ndarray:
    """Build a named TO matrix (``cs`` | ``ss`` | ``ra`` | ``block``).
    ``loads=`` builds the ragged variant (per-worker loads, trailing slots
    ``MASKED``) for every schedule that supports it."""
    try:
        sched = SCHEDULES[name.lower()]
    except KeyError:
        raise ValueError(f"unknown schedule {name!r}; have {sorted(SCHEDULES)}")
    return sched(n, r, **kw)


# --------------------- adaptive row assignment -------------------------------

def greedy_row_assignment(C: np.ndarray, speed_est=None, *,
                          gamma: float = 0.5, need=None) -> np.ndarray:
    """Assign workers to the rows of base TO matrix ``C`` from estimated
    per-worker delays: fastest workers pick first, each taking the row whose
    leading slots cover the least-covered tasks.

    Parameters
    ----------
    C:         base (n, r) TO matrix whose rows get re-assigned.
    speed_est: length-n estimated per-task delay of each worker (smaller =
               faster); ``None`` means no feedback yet (uniform speeds —
               the greedy then just spaces coverage, e.g. rows 0, r, 2r, …
               of a cyclic matrix go to the first pickers).
    gamma:     per-slot coverage discount: slot j of a chosen row adds
               ``gamma**j / speed_est[w]`` coverage to its task — earlier
               slots (and faster workers) count for more, mirroring eq. (1)'s
               sequential arrivals.

    Returns ``worker_of_row``, a permutation with ``worker_of_row[p] = w``
    meaning worker ``w`` executes row ``p``.  The induced effective schedule
    is ``C_eff[w] = C[row_of_worker[w]]`` with ``row_of_worker`` the inverse
    permutation (``AdaptiveScheduler.matrix`` builds it).

    ``need`` (optional, length-n bool over *tasks*) marks tasks whose
    previous-round results were never delivered (reissue deadline policy):
    rows containing a needed task are picked before any row without one,
    so the fastest workers re-gather the backlog first.

    This delegates to the batched JAX implementation (one source of truth),
    so training loops and the fused rounds engine pick identical rows for
    identical feedback.
    """
    C = np.asarray(C)
    n, r = C.shape
    est = (np.ones(n, np.float32) if speed_est is None
           else np.asarray(speed_est, np.float32))
    if est.shape != (n,):
        raise ValueError(f"speed_est must have shape ({n},), got {est.shape}")
    C_tup = tuple(tuple(int(v) for v in row) for row in C)
    if need is None:
        fn = _jitted_greedy(C_tup, float(gamma))
        return np.asarray(fn(jnp.asarray(est)[None])[0], np.int64)
    nd = np.asarray(need)
    if nd.shape != (n,):
        raise ValueError(f"need must have shape ({n},), got {nd.shape}")
    fn = _jitted_greedy_need(C_tup, float(gamma))
    return np.asarray(
        fn(jnp.asarray(est)[None],
           jnp.asarray(nd, jnp.float32)[None])[0], np.int64)


@functools.lru_cache(maxsize=None)
def _jitted_greedy(C_tup: tuple, gamma: float):
    C = np.asarray(C_tup, np.int64)
    return jax.jit(lambda est: greedy_row_assignment_batch(C, est,
                                                           gamma=gamma))


@functools.lru_cache(maxsize=None)
def _jitted_greedy_need(C_tup: tuple, gamma: float):
    C = np.asarray(C_tup, np.int64)
    return jax.jit(lambda est, need: greedy_row_assignment_batch(
        C, est, gamma=gamma, need=need))


GREEDY_IMPLS = ("auto", "scan", "kernel")


def _resolve_greedy_impl(impl: str | None) -> str:
    """``None``/``"auto"`` -> the Pallas kernel on compiled backends
    (TPU/GPU), the pure-jnp scan on CPU (where Pallas only interprets);
    explicit ``"scan"``/``"kernel"`` forces one path (tests, debugging)."""
    if impl in (None, "auto"):
        from ..kernels.gram_matvec import default_interpret
        return "scan" if default_interpret() else "kernel"
    if impl not in ("scan", "kernel"):
        raise ValueError(f"unknown greedy impl {impl!r}; choose from "
                         f"{GREEDY_IMPLS}")
    return impl


@functools.lru_cache(maxsize=None)
def _greedy_matrices(C_tup: tuple, gamma: float):
    """Static pick-loop matrices of a TO matrix: the coverage-weight
    matrix ``W[p, t] = sum_j gamma**j * [C[p, j] == t]`` (active slots
    only) and the 0/1 row-covers-task incidence ``A[p, t]``.  With these,
    greedy scores are ``cov @ W.T`` and the reissue row-priority is
    ``need @ A.T > 0`` — no gathers in the pick loop.  Rows with distinct
    active tasks (what ``validate_to_matrix`` enforces) make the matvec
    arithmetic term-for-term identical to the per-slot gather form."""
    C = np.asarray(C_tup)
    n, r = C.shape
    active = C != MASKED
    disc = gamma ** np.arange(r)
    W = np.zeros((n, n), np.float32)
    A = np.zeros((n, n), np.float32)
    for p in range(n):
        for j in range(r):
            if active[p, j]:
                W[p, C[p, j]] += np.float32(disc[j])
                A[p, C[p, j]] = 1.0
    return W, A


def greedy_row_assignment_batch(C: np.ndarray, est: jax.Array, *,
                                gamma: float = 0.5,
                                need: jax.Array | None = None,
                                impl: str | None = None) -> jax.Array:
    """Batched JAX twin of ``greedy_row_assignment``: ``est`` has shape
    (..., n); returns ``worker_of_row`` of the same shape (int32).  Pure and
    jit/scan-friendly (``C`` is baked in at trace time); used per-trial
    inside the fused rounds engine.  ``C`` may be ragged: ``MASKED`` slots
    contribute no coverage (their weight is statically zeroed).

    The pick loop runs as dense per-step matmuls against the static
    coverage-weight matrix of ``C`` (see ``_greedy_matrices``), either as
    a pure-jnp scan (``repro.kernels.ref.greedy_assign_ref``) or as the
    Pallas kernel (``repro.kernels.ops.greedy_assign``); ``impl`` selects
    (``None``/``"auto"`` = kernel on compiled backends, scan on CPU).

    ``need`` (traced, (..., n) or (n,) over tasks, nonzero = needed) is the
    reissue priority: while any un-taken row still holds a needed task, the
    picker's argmin runs over those rows only.  ``need=None`` (and an
    all-zero ``need``) keeps the established pick order bit-exactly."""
    from ..kernels import ops as kernel_ops
    from ..kernels.ref import greedy_assign_ref
    C = np.asarray(C)
    n, r = C.shape
    C_tup = tuple(tuple(int(v) for v in row) for row in C)
    W, A = _greedy_matrices(C_tup, float(gamma))
    Wj = jnp.asarray(W)

    batch = est.shape[:-1]
    flat = est.reshape((-1, n))
    order = jnp.argsort(flat, axis=-1).astype(jnp.int32)  # stable; fast 1st
    epick = jnp.maximum(jnp.take_along_axis(flat, order, axis=-1),
                        jnp.float32(1e-30))
    need_row = None
    if need is not None:
        ndf = jnp.broadcast_to(jnp.asarray(need, jnp.float32),
                               est.shape).reshape((-1, n))
        need_row = (ndf > 0).astype(jnp.float32) @ jnp.asarray(A).T

    if _resolve_greedy_impl(impl) == "kernel":
        out = kernel_ops.greedy_assign(Wj, order, epick, need_row)
    else:
        out = greedy_assign_ref(Wj, order, epick, need_row)
    return out.reshape(batch + (n,))


# --------------------- adaptive load re-balancing ----------------------------

def greedy_load_rebalance(speed_est, loads=None, *, total: int | None = None,
                          r_max: int, min_load: int = 1,
                          steps: int | None = None) -> np.ndarray:
    """Re-allocate whole computation slots between workers from estimated
    per-task delays, under a fixed total budget (Egger et al.,
    arXiv:2304.08589: *reducing* a slow worker's load beats re-ordering its
    tasks).

    Starting from ``loads`` (or an as-even-as-possible split of ``total``),
    the greedy repeatedly moves one slot from the worker with the largest
    estimated finish time ``est[w] * loads[w]`` to the worker whose
    post-move finish ``est[w'] * (loads[w'] + 1)`` is smallest, whenever the
    move strictly lowers the donor's finish below nothing it raises —
    i.e. classic makespan descent.  Bounds: ``min_load <= loads[w] <=
    r_max`` and ``sum(loads)`` is invariant (the total computation budget).

    ``speed_est`` may contain ``+inf`` for workers never yet observed
    (censored feedback): they shed slots down to ``min_load`` as soon as any
    finite-estimate worker has headroom.  With no observations at all
    (all-``inf`` estimates, or ``None``/all-equal estimates on a uniform
    allocation) the allocation is returned unchanged; equal *finite*
    estimates on an uneven allocation still descend toward the even split
    (that is the makespan greedy doing its job).

    Returns the new per-worker load vector (int64): the descent's fixed
    point.  Delegates to the batched JAX implementation (one source of
    truth with the fused rounds engine), which computes that fixed point
    in closed form.
    """
    if loads is None:
        if total is None or speed_est is None:
            raise ValueError("need an initial loads vector, or a total "
                             "budget plus a speed_est to size it from")
        n = np.asarray(speed_est).shape[0]
        base, extra = divmod(int(total), n)
        lv = np.full(n, base, np.int64)
        lv[:extra] += 1                    # as-even-as-possible split
    else:
        lv = np.asarray(loads, np.int64)
    n = lv.shape[0]
    if total is not None and int(lv.sum()) != int(total):
        raise ValueError(f"loads sum {lv.sum()} != total budget {total}")
    if not 1 <= min_load <= lv.min():
        raise ValueError(f"need 1 <= min_load <= min(loads); got "
                         f"min_load={min_load}, loads min {lv.min()}")
    if lv.max() > r_max:
        raise ValueError(f"max load {lv.max()} exceeds r_max={r_max}")
    est = (np.ones(n, np.float32) if speed_est is None
           else np.asarray(speed_est, np.float32))
    if est.shape != (n,):
        raise ValueError(f"speed_est must have shape ({n},), got {est.shape}")
    fn = _jitted_rebalance(tuple(int(v) for v in lv), int(r_max),
                           int(min_load), steps)
    return np.asarray(fn(jnp.asarray(est)[None])[0], np.int64)


@functools.lru_cache(maxsize=None)
def _jitted_rebalance(loads_tup: tuple, r_max: int, min_load: int,
                      steps: int | None):
    loads = np.asarray(loads_tup, np.int64)
    return jax.jit(lambda est: greedy_load_rebalance_batch(
        est, loads, r_max=r_max, min_load=min_load, steps=steps))


def greedy_load_rebalance_batch(est: jax.Array, loads: np.ndarray, *,
                                r_max: int, min_load: int = 1,
                                steps: int | None = None) -> jax.Array:
    """Batched JAX twin of ``greedy_load_rebalance``: ``est`` has shape
    (..., n) (non-negative delay estimates, ``+inf`` = never observed),
    ``loads`` is the static initial allocation; returns per-worker loads of
    the same batch shape (int32), each summing to ``sum(loads)``.  Pure and
    jit/scan-friendly; used per-round inside the fused rounds engine.

    The makespan descent (``_rebalance_descent``) moves a slot only when
    that strictly lowers a cost, so no slot moves twice and it reaches its
    fixed point within ``n * (r_max - min_load)`` moves.  There, every held
    extra slot costs no more than any unheld one: the held set is the
    ``T = sum(loads) - n * min_load`` cheapest of the slot costs
    ``est[w] * j``, ``j`` in ``(min_load, r_max]`` (the float32 products
    the descent forms).  This computes that fixed point with one sort per
    row: each worker holds ``min_load`` plus its slots costing at most the
    T-th cheapest.  Where the T-th cheapest is ``+inf`` the observed
    workers cannot hold the total, and the descent's donors (the lowest
    index first among the ``+inf`` finishers) leave the unobserved workers
    their initial loads from the highest index down.

    A finite tie at the threshold (T-th cheapest == (T+1)-th) makes the
    descent's result depend on its path; where any row of the batch has
    one, ``lax.cond`` runs the descent for the whole batch instead, so the
    result equals the descent's bit for bit on every input.  ``steps``
    bounds the descent's single-slot moves (default ``n * r_max``); below
    ``n * (r_max - min_load)`` a truncated descent has no closed form, and
    it runs alone."""
    loads = np.asarray(loads, np.int64)
    n = loads.shape[0]
    if not min_load <= loads.min() <= loads.max() <= r_max:
        raise ValueError(f"need {min_load} <= loads <= r_max={r_max}; got "
                         f"{loads.tolist()}")
    if steps is None:
        steps = int(n * r_max)
    batch = est.shape[:-1]
    flat = est.reshape((-1, n))
    if steps < n * (r_max - min_load):
        out = _rebalance_descent(flat, loads, r_max, min_load, steps)
    else:
        closed, tied = _rebalance_fixed_point(flat, loads, r_max, min_load)
        out = jax.lax.cond(
            tied.any(),
            lambda: _rebalance_descent(flat, loads, r_max, min_load, steps),
            lambda: closed)
    return out.reshape(batch + (n,))


def _rebalance_fixed_point(est, loads: np.ndarray, r_max: int,
                           min_load: int):
    """The descent's fixed point in closed form, ``est`` (B, n): returns
    ``(loads (B, n) int32, tied (B,) bool)``, the loads being the
    descent's wherever ``tied`` is False."""
    B, n = est.shape
    m = r_max - min_load                      # extra slots a worker may hold
    T = int(loads.sum()) - n * min_load       # extra slots held in all
    l0 = jnp.broadcast_to(jnp.asarray(loads, jnp.int32), (B, n))
    if T == 0 or T == n * m:                  # nothing can move
        return l0, jnp.zeros((B,), bool)
    j = jnp.arange(min_load + 1, r_max + 1, dtype=jnp.float32)
    cost = est[:, :, None] * j                # (B, n, m), as the descent
    srt = jnp.sort(cost.reshape(B, n * m), axis=-1)
    thr, nxt = srt[:, T - 1], srt[:, T]
    fin = jnp.isfinite(cost)
    take = ((cost <= thr[:, None, None]) & fin).sum(-1)
    # thr = +inf: the finite slots are all held, and the total beyond them
    # stays with the +inf finishers, shed from the lowest index first.
    nfin = fin.sum(-1)
    short = T - nfin.sum(-1)
    room = jnp.maximum(l0 - min_load - nfin, 0)
    above = jnp.cumsum(room[:, ::-1], axis=-1)[:, ::-1] - room
    keep = jnp.clip(short[:, None] - above, 0, room)
    out = (min_load + take + keep).astype(jnp.int32)
    return out, jnp.isfinite(thr) & (thr == nxt)


def _rebalance_descent(est, loads: np.ndarray, r_max: int, min_load: int,
                       steps: int):
    """The makespan descent, ``est`` (B, n): ``steps`` times, move one slot
    from the largest finisher ``est[d] * loads[d]`` to the cheapest extra
    slot ``est[w] * (loads[w] + 1)`` when that strictly lowers the cost,
    within ``min_load <= loads <= r_max``."""
    l0 = jnp.asarray(loads, jnp.int32)
    ninf = jnp.float32(-np.inf)
    pinf = jnp.float32(np.inf)

    def one(e):                                       # e (n,)
        def move(l, _):
            lf = l.astype(jnp.float32)
            finish = e * lf                           # est finish per worker
            can_give = l > min_load
            can_take = l < r_max
            give = jnp.where(can_give, finish, ninf)
            take = jnp.where(can_take, e * (lf + 1.0), pinf)
            d = jnp.argmax(give)                      # slowest finisher
            w = jnp.argmin(take)                      # cheapest extra slot
            # move only if it strictly lowers the donor's finish below the
            # receiver's post-move finish (makespan descent; `inf > inf`
            # is False, so an all-inf/no-feedback round keeps the split).
            ok = (give[d] > take[w]) & can_give[d] & can_take[w] & (d != w)
            l = jnp.where(ok, l.at[d].add(-1).at[w].add(1), l)
            return l, None

        l, _ = jax.lax.scan(move, l0, None, length=steps)
        return l

    return jax.vmap(one)(est)


def censored_feedback_update(est: jax.Array, t1: jax.Array,
                             arrivals: jax.Array, t_done, *,
                             beta: float = 0.7) -> jax.Array:
    """One censored-feedback step — the single source of truth shared by
    ``AdaptiveScheduler.observe`` and the fused rounds engine
    (``montecarlo.sweep_rounds(..., censored_feedback=True)``), so training
    loops and MC estimates apply identical update rules to identical
    observations.

    ``est`` (..., n) is the per-worker delay estimate with +inf marking
    workers never yet observed; ``t1``/``arrivals`` (..., n, r) are the
    round's per-slot compute delays and per-message arrival times, both
    worker-major; ``t_done`` (scalar or (...,)) the round's completion time.
    Only slots whose message arrived by ``t_done`` are observed: observed
    workers get their masked-mean compute delay (replace on first
    observation, EMA with weight ``beta`` on history after), silent workers
    keep their previous estimate.  Returns the new ``est``.

    +inf-safe: a censored slot (fault-killed worker, ``arrivals`` and/or
    ``t1`` = +inf) is never observed, even when ``t_done`` is itself +inf
    (``wait`` policy with fewer than k survivors) — ``inf <= inf`` must
    not count as an arrival, and masked +inf delays must not poison the
    observed mean with ``inf * 0 = nan``.
    """
    td = jnp.asarray(t_done)[..., None, None]
    arr = jnp.asarray(arrivals)
    mobs = (arr <= td) & jnp.isfinite(arr)
    cnt = mobs.sum(axis=-1)
    obs = jnp.where(cnt > 0,
                    jnp.where(mobs, jnp.asarray(t1), 0.0).sum(axis=-1)
                    / jnp.maximum(cnt, 1), 0.0)
    est = jnp.asarray(est)
    seen = jnp.isfinite(est)
    upd = jnp.where(seen, beta * est + (1.0 - beta) * obs, obs)
    return jnp.where(cnt > 0, upd, est)


class AdaptiveScheduler:
    """Stateful round-to-round re-permutation of a base TO matrix.

    Call ``matrix()`` before each round for the effective schedule,
    ``observe(t1)`` after it with the round's per-worker compute delays
    ((n,) means or the raw (n, r) slot delays).  Feedback is an EMA with
    weight ``beta`` on history, so transient hiccups don't thrash the
    assignment but persistent stragglers migrate to low-impact rows.

    Passing ``arrivals``/``t_done`` to ``observe`` censors the feedback to
    what a real master sees: only slots whose message reached the master
    before the round completed are observed.  Workers that delivered
    nothing keep their previous estimate; a worker never yet observed sits
    at +inf, i.e. is ranked slowest until it first delivers (principled: a
    worker that never beat the round deadline *is* effectively slowest).

    Ragged loads: the base ``C`` may itself be ragged (rows carry their
    loads through the re-permutation), or — with ``rebalance=True`` and a
    dense base ``C`` whose width is the per-worker load *cap* — the
    scheduler additionally re-allocates whole slots between workers each
    round (``greedy_load_rebalance``) under the fixed total budget
    ``sum(loads)``: slow workers shed slots to fast ones.  ``loads()``
    returns the coming round's per-worker loads; ``matrix()`` masks the
    effective schedule accordingly.

    Crash awareness (fault tolerance, see ``cluster.FaultProcess``): with
    ``dead_after`` set, a worker that has delivered nothing for that many
    consecutive observed rounds is presumed *dead* — its estimate is
    forced to +inf so the greedy assignment hands it the least-covering
    rows (survivors repair coverage by taking the high-coverage rows
    first) and, under ``rebalance``, it sheds load down to ``min_load``.
    With ``target_k`` set, ``matrix()`` additionally verifies the
    surviving assignment still spans >= ``target_k`` distinct tasks and
    raises a ``ValueError`` naming the shortfall when degradation cannot
    be graceful.  ``set_need`` feeds the reissue deadline policy: tasks
    whose results were never delivered get re-gathered first next round.
    """

    def __init__(self, C: np.ndarray, *, beta: float = 0.7,
                 gamma: float = 0.5, loads=None, rebalance: bool = False,
                 min_load: int = 1, dead_after: int | None = None,
                 target_k: int | None = None):
        self.C = np.asarray(C)
        self.rebalance = bool(rebalance)
        if self.rebalance:
            if (self.C == MASKED).any():
                raise ValueError("rebalance needs a dense base matrix (its "
                                 "width is the per-worker load cap); pass "
                                 "the budget via loads=")
            validate_to_matrix(self.C)
            if loads is None:
                raise ValueError("rebalance needs an initial loads budget "
                                 "below the grid width (loads=)")
            self.base_loads, _ = _check_loads(self.C.shape[0], loads,
                                              self.C.shape[1])
        else:
            validate_to_matrix(self.C, loads=loads)
            self.base_loads = loads_of_matrix(self.C)
        self.min_load = int(min_load)
        self.beta = float(beta)
        self.gamma = float(gamma)
        if dead_after is not None and dead_after < 1:
            raise ValueError(f"dead_after must be >= 1, got {dead_after}")
        if target_k is not None and not 1 <= target_k <= self.C.shape[0]:
            raise ValueError(f"target_k must be in [1, {self.C.shape[0]}], "
                             f"got {target_k}")
        self.dead_after = dead_after
        self.target_k = target_k
        self.est: np.ndarray | None = None
        self.silent = np.zeros(self.C.shape[0], np.int64)
        self._need: np.ndarray | None = None
        self._assignment: np.ndarray | None = None   # valid until observe()
        self._loads: np.ndarray | None = None

    def dead_workers(self) -> np.ndarray:
        """Bool (n,): workers presumed dead — nothing delivered for
        ``dead_after`` consecutive observed rounds (all-False when crash
        detection is off)."""
        if self.dead_after is None:
            return np.zeros(self.C.shape[0], bool)
        return self.silent >= self.dead_after

    def _effective_est(self) -> np.ndarray | None:
        """Feedback estimates with presumed-dead workers censored to +inf
        (ranked slowest: they pick rows last and shed load first)."""
        dead = self.dead_workers()
        if not dead.any():
            return self.est
        base = (np.ones(self.C.shape[0], np.float64) if self.est is None
                else self.est)
        return np.where(dead, np.inf, base)

    def set_need(self, need) -> None:
        """Mark tasks to re-gather first next round (reissue policy):
        ``need`` is a length-n bool over tasks (or None to clear)."""
        nd = None if need is None else np.asarray(need, bool)
        if nd is not None and nd.shape != (self.C.shape[0],):
            raise ValueError(f"need must have shape ({self.C.shape[0]},), "
                             f"got {nd.shape}")
        self._need = nd if nd is not None and nd.any() else None
        self._assignment = None

    def worker_of_row(self) -> np.ndarray:
        if self._assignment is None:
            self._assignment = greedy_row_assignment(
                self.C, self._effective_est(), gamma=self.gamma,
                need=self._need)
        return self._assignment

    def row_of_worker(self) -> np.ndarray:
        w_of_row = self.worker_of_row()
        inv = np.empty_like(w_of_row)
        inv[w_of_row] = np.arange(len(w_of_row))
        return inv

    def loads(self) -> np.ndarray:
        """Per-worker loads for the coming round: the assigned rows' own
        loads, re-balanced from feedback when ``rebalance`` is on (workers
        with no estimate yet count as slowest: +inf)."""
        if not self.rebalance:
            return self.base_loads[self.row_of_worker()]
        if self._loads is None:
            est = self._effective_est()
            if est is None:
                est = np.full(self.C.shape[0], np.inf)
            self._loads = greedy_load_rebalance(
                est, self.base_loads, r_max=self.C.shape[1],
                min_load=self.min_load)
        return self._loads

    def matrix(self) -> np.ndarray:
        """The effective TO matrix for the coming round: row ``w`` is what
        worker ``w`` executes (``MASKED`` beyond worker ``w``'s load).

        With crash detection on (``dead_after`` + ``target_k``), verifies
        the rows held by surviving workers still span >= ``target_k``
        distinct tasks — the greedy repair (dead workers rank slowest, so
        survivors picked the high-coverage rows first) usually guarantees
        this, but when too many workers died for any assignment to cover
        k tasks, degradation cannot be graceful and this raises instead
        of letting a round hang forever."""
        M = self.C[self.row_of_worker()]
        if self.rebalance:
            M = mask_matrix_loads(M, self.loads())
        dead = self.dead_workers()
        if self.target_k is not None and dead.any():
            alive_rows = M[~dead]
            act = alive_rows[alive_rows != MASKED]
            covered = int(np.unique(act).size)
            if covered < self.target_k:
                raise ValueError(
                    f"graceful degradation impossible: {int(dead.sum())} of "
                    f"{self.C.shape[0]} workers presumed dead (no delivery "
                    f"for {self.dead_after} consecutive rounds) and the "
                    f"surviving assignment covers only {covered} distinct "
                    f"tasks < k={self.target_k}; lower k, raise the "
                    f"per-worker load, or raise dead_after")
        return M

    def observe(self, t1, *, arrivals=None, t_done=None) -> None:
        n = self.C.shape[0]
        obs = np.asarray(t1, np.float64)
        if (arrivals is None) != (t_done is None):
            raise ValueError("censored feedback needs BOTH arrivals and "
                             "t_done (or neither)")
        if arrivals is not None:
            # censored: only slots whose message arrived by t_done count.
            # Delegates to the shared update rule (one source of truth
            # with the fused rounds engine).
            arr = np.asarray(arrivals, np.float64)
            if obs.ndim != 2 or obs.shape[0] != n or arr.shape != obs.shape:
                raise ValueError(
                    f"censored feedback needs per-slot (n={n}, r) compute "
                    f"delays and matching arrivals; got {obs.shape} and "
                    f"{arr.shape}")
            est = (np.full(n, np.inf) if self.est is None else self.est)
            self.est = np.asarray(censored_feedback_update(
                jnp.asarray(est, jnp.float32), obs, arr, float(t_done),
                beta=self.beta), np.float64)
            delivered = (np.isfinite(arr) & (arr <= float(t_done))).any(-1)
            self.silent = np.where(delivered, 0, self.silent + 1)
            self._assignment = None
            self._loads = None
            return
        if obs.ndim == 2:
            # +inf slot delays (fault-censored) must not drag the row mean
            # to inf — average the finite slots only
            fin = np.isfinite(obs)
            cnt = fin.sum(-1)
            obs = np.where(cnt > 0,
                           np.where(fin, obs, 0.0).sum(-1)
                           / np.maximum(cnt, 1), np.inf)
        if obs.shape != (n,):
            raise ValueError(f"feedback must be (n,) or (n, r) for "
                             f"n={n}; got {obs.shape}")
        delivered = np.isfinite(obs)
        if self.est is None:
            # never-delivering workers start at the +inf censored sentinel
            self.est = np.where(delivered, obs, np.inf)
        else:
            # replace-on-first for workers still at the +inf never-observed
            # sentinel (left there by earlier censored rounds) — EMAing the
            # sentinel would pin them at +inf forever.  A +inf observation
            # (dead worker this round) keeps the previous estimate.
            seen = np.isfinite(self.est)
            upd = np.where(seen,
                           self.beta * self.est + (1.0 - self.beta) * obs,
                           obs)
            self.est = np.where(delivered, upd, self.est)
        self.silent = np.where(delivered, 0, self.silent + 1)
        self._assignment = None
        self._loads = None
