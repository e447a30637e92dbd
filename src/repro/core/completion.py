"""Arrival-time / completion-time computation (paper eqs. 1–6, 46).

Everything is expressed as vectorized JAX ops over a leading ``trials`` axis
so Monte-Carlo evaluation of the average completion time is one jitted call.

Conventions
-----------
* ``C``   — TO matrix, shape (n, r), task indices in [0, n).
* ``T1``  — per-slot computation delays, shape (trials, n, r). ``T1[t,i,j]``
            is the compute delay of worker ``i``'s j-th *slot* (the task in
            that slot is ``C[i, j]``).
* ``T2``  — per-slot communication delays, same shape.

Derived:
* slot arrival   ``s[t,i,j] = sum_{m<=j} T1[t,i,m] + T2[t,i,j]``   (eq. 1)
* task arrival   ``tau[t,p] = min over slots with C[i,j]==p``      (eq. 2)
* completion     ``t_C(r,k) = k-th smallest of tau``                (eq. 6)
* oracle LB      ``k-th smallest of all n*r slot arrivals``         (eq. 46)

``message_arrival_times`` generalizes eq. (1) to an intra-round message
budget (paper Sec. V-C): with ``messages`` messages per worker per round, a
slot's result becomes available when its *message* is sent — at the closing
slot of its group — plus that message's communication delay draw.
``messages = r`` is eq. (1) bit-exactly (per-slot sends); ``messages = 1``
is the one-shot send the coded PC baseline uses (eqs. 51-52).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import montecarlo

__all__ = [
    "slot_arrival_times", "message_arrival_times", "message_slot_layout",
    "row_layout_is_identity", "apply_row_layout", "task_arrival_times",
    "completion_time", "lower_bound_time", "first_k_distinct_mask",
    "winner_mask_gather", "simulate_completion", "simulate_lower_bound",
    "mean_completion_time",
]

Array = jax.Array
INF = jnp.inf


def slot_arrival_times(T1: Array, T2: Array) -> Array:
    """eq. (1): s[..., i, j] = cumsum_j(T1)[..., i, j] + T2[..., i, j]."""
    return jnp.cumsum(T1, axis=-1) + T2


def message_slot_layout(loads, r: int, messages: int,
                        comm_eps: float = 0.0):
    """Static per-row message layout for a (possibly ragged) slot grid:
    returns ``(smap, offsets, active)`` — the (n, r) closing-slot remap,
    per-slot overhead offsets (None when ``comm_eps`` is 0) and active-slot
    mask (None when dense) — shared by ``message_arrival_times`` and the
    aggregator's row-major arrival path."""
    lv = np.asarray(loads, np.int64)
    n = lv.shape[0]
    smap = np.broadcast_to(np.arange(r), (n, r)).copy()
    off = np.zeros((n, r), np.float32)
    active = np.zeros((n, r), bool)
    for i, l in enumerate(lv):
        mi = min(int(messages), int(l))
        smap[i, :l] = montecarlo.message_slot_map(int(l), mi)
        b = montecarlo.message_boundaries(int(l), mi)
        off[i, :l] = comm_eps * (np.searchsorted(b, np.arange(int(l))) + 1)
        active[i, :l] = True
    return (smap, off if comm_eps else None,
            None if active.all() else active)


def row_layout_is_identity(layout) -> bool:
    """True when a ``message_slot_layout`` result is a no-op (dense,
    per-slot sends, no overhead) — callers then skip ``apply_row_layout``
    entirely, keeping the established fast path bit-identical."""
    smap, off, act = layout
    n, r = smap.shape
    return (off is None and act is None
            and np.array_equal(smap, np.broadcast_to(np.arange(r), (n, r))))


def apply_row_layout(s: Array, layout) -> Array:
    """Apply a static per-row message layout (``message_slot_layout``) to
    per-slot arrivals ``s`` (..., n, r): closing-slot remap, overhead
    offsets, +inf beyond each row's load.  The single implementation
    shared by ``message_arrival_times``, the aggregator, and the train
    step."""
    smap, off, act = layout
    out = jnp.take_along_axis(
        s, jnp.broadcast_to(jnp.asarray(smap), s.shape), axis=-1)
    if off is not None:
        out = out + jnp.asarray(off)
    if act is not None:
        out = jnp.where(jnp.asarray(act), out, INF)
    return out


def message_arrival_times(T1: Array, T2: Array, messages: int, *,
                          loads=None, comm_eps: float = 0.0) -> Array:
    """Generalized eq. (1) for an intra-round message budget: slot ``j``'s
    result arrives when its message closes — cumulative compute through the
    group's closing slot ``b(j)`` plus that message's communication draw
    (``T2[..., b(j)]``, see ``cluster.message_comm_delays``).  Returns the
    same (..., n, r) layout as ``slot_arrival_times``; ``messages == r``
    reproduces it bit-exactly.

    ``loads`` makes the grouping per-worker (worker ``w`` groups its
    ``loads[w]`` active slots into ``min(messages, loads[w])`` messages;
    its masked trailing slots come out +inf — never available).
    ``comm_eps`` adds the serialized per-message protocol overhead: a
    worker's l-th message lands ``(l + 1) * comm_eps`` late."""
    r = T1.shape[-1]
    n = T1.shape[-2]
    s = slot_arrival_times(T1, T2)
    if loads is None and not comm_eps:
        if int(messages) == r:
            return s
        return s[..., jnp.asarray(montecarlo.message_slot_map(r, messages))]
    lv = (np.full(n, r, np.int64) if loads is None
          else np.asarray(loads, np.int64))
    return apply_row_layout(s, message_slot_layout(lv, r, messages,
                                                   comm_eps))


def _static_active(C) -> np.ndarray | None:
    """Static active-slot mask of a (possibly ragged) TO matrix, or None
    when ``C`` is all-active — or a traced array, which the round APIs only
    produce for dense schedules (ragged C is always static)."""
    try:
        active = np.asarray(C) >= 0
    except Exception:                      # traced C: dense by contract
        return None
    return None if active.all() else active


def task_arrival_times(C: Array, s: Array, n: int) -> Array:
    """eq. (2): per-task earliest arrival across all (worker, slot) holding
    the task. Tasks never assigned get +inf. Shapes: C (n_w, r), s
    (..., n_w, r) -> (..., n).  ``C`` may be ragged: ``MASKED`` (-1) slots
    are statically excluded (their arrivals read as +inf)."""
    active = _static_active(C)
    if active is not None:
        # masked slots never deliver: +inf before the scatter-min (the -1
        # index would otherwise wrap onto task n-1)
        s = jnp.where(jnp.asarray(active), s, INF)
    Cf = jnp.asarray(C).reshape(-1)                  # (n_w * r,)
    sf = s.reshape(s.shape[:-2] + (-1,))             # (..., n_w * r)
    init = jnp.full(s.shape[:-2] + (n,), INF, s.dtype)
    return init.at[..., Cf].min(sf)


def completion_time(tau: Array, k: int) -> Array:
    """eq. (6): time the master holds k distinct results = k-th order
    statistic of task arrivals. tau (..., n) -> (...,)."""
    return jnp.sort(tau, axis=-1)[..., k - 1]


def lower_bound_time(s: Array, k: int) -> Array:
    """eq. (46): adaptive lower bound — with delay realizations known ahead,
    an oracle TO matrix makes the first k received results distinct, so the
    completion time is the k-th order statistic over ALL n*r slot arrivals."""
    sf = s.reshape(s.shape[:-2] + (-1,))
    return jnp.sort(sf, axis=-1)[..., k - 1]


def first_k_distinct_mask(C: Array, s: Array, n: int, k: int, *,
                          deadline: float | None = None
                          ) -> Tuple[Array, Array]:
    """Which (worker, slot) results the master uses: the earliest copy of
    each of the k earliest-arriving distinct tasks.

    Returns ``(weights, t_done)`` where ``weights`` has shape
    ``s.shape`` (…, n_w, r): per-slot aggregation weight (0 for unused slots;
    winners of selected tasks share weight 1 per task — ties averaged), and
    ``t_done`` (…,) is the completion time. Everything is differentiable-free
    masking, usable inside a jitted train step.

    With per-slot sends exactly k tasks are selected almost surely.  Under a
    reduced message budget (``message_arrival_times``) arrival ties are
    structural — the closing message can deliver more distinct tasks than
    were still missing — so ``weights`` may sum to more than ``k``; consumers
    normalize by the realized sum (see ``StragglerAggregator.combine``).

    ``deadline`` caps the round (fault tolerance, see
    ``cluster.FaultProcess``): the master closes at
    ``min(t_done, deadline)`` and only results arrived by then win —
    fewer than k when arrivals are late or censored to +inf, so a
    fully-missed round has all-zero weights.
    """
    active = _static_active(C)             # static, before any jnp tracing
    tau = task_arrival_times(C, s, n)                    # (..., n)
    return _winner_weights(jnp.asarray(C), s, tau, k, active,
                           deadline=deadline)


def winner_mask_gather(C: Array, plan: np.ndarray, s: Array, n: int, k: int,
                       *, deadline: float | None = None
                       ) -> Tuple[Array, Array]:
    """``first_k_distinct_mask`` with task arrivals computed through the
    fused engine's static gather layout (``task_gather_plan(C, n)``) instead
    of a dynamic scatter-min — the TPU-friendly form used by the round API
    (aggregator / train step hot paths)."""
    active = _static_active(C)             # static, before any jnp tracing
    tau = montecarlo.task_arrival_times_gather(plan, s)  # (..., n)
    return _winner_weights(jnp.asarray(C), s, tau, k, active,
                           deadline=deadline)


def _winner_weights(C: Array, s: Array, tau: Array, k: int,
                    active: np.ndarray | None, *,
                    deadline: float | None = None) -> Tuple[Array, Array]:
    t_done = completion_time(tau, k)                     # (...,)
    if deadline is not None:
        # close the round at the deadline with whatever has arrived —
        # t_done stays finite even when fewer than k tasks ever arrive
        t_done = jnp.minimum(t_done, jnp.asarray(deadline, tau.dtype))
    # +inf-safe: a censored task (tau = +inf, fault-killed worker) must
    # not be "selected" when t_done is itself +inf (inf <= inf is True)
    selected = (tau <= t_done[..., None]) & jnp.isfinite(tau)
    # winner slots: slot arrival equals its task's earliest arrival
    tau_at_slot = tau[..., C]                            # (..., n_w, r)
    sel_at_slot = selected[..., C]                       # (..., n_w, r)
    is_winner = (s <= tau_at_slot) & sel_at_slot
    if active is not None:
        # ragged rows: a MASKED slot's -1 index aliases task n-1 above, so
        # statically bar masked slots from winning (their weight is 0)
        is_winner = is_winner & jnp.asarray(active)
    # normalize per task so duplicated winners (measure-zero ties) average
    ones = jnp.where(is_winner, 1.0, 0.0)
    per_task_count = jnp.zeros_like(tau).at[..., C.reshape(-1)].add(
        ones.reshape(ones.shape[:-2] + (-1,)))
    cnt_at_slot = jnp.maximum(per_task_count[..., C], 1.0)
    weights = ones / cnt_at_slot
    return weights, t_done


# ---------------- Monte-Carlo drivers ----------------------------------------
# Thin wrappers over the fused sweep engine (see montecarlo.py): one
# per-trial PRNG subkey stream, static gather layout for eq. (2), chunkable
# trial streaming, and a rank count for single-k order statistics.

def simulate_completion(C: np.ndarray, model, k: int, *, trials: int = 10000,
                        seed: int = 0, chunk: int | None = None) -> Array:
    """Sample ``trials`` rounds of the schedule ``C`` under ``model`` and
    return the completion-time samples, shape (trials,).  ``C`` may be
    ragged (trailing ``MASKED`` sentinels)."""
    n = np.asarray(C).shape[0]
    return montecarlo.completion_samples(
        montecarlo.to_spec("to", C), model, n, trials=trials, seed=seed,
        chunk=chunk, k=k)


def simulate_lower_bound(model, n: int, r: int | None = None,
                         k: int = 1, *, trials: int = 10000,
                         seed: int = 0, chunk: int | None = None,
                         loads=None) -> Array:
    """Monte-Carlo eq. (44): samples of the oracle k-th order statistic.
    ``loads`` generalizes the bound to ragged per-worker loads (the k-th
    order statistic over all ``sum(loads)`` active slot arrivals)."""
    return montecarlo.completion_samples(
        montecarlo.lb_spec(r, loads=loads), model, n, trials=trials,
        seed=seed, chunk=chunk, k=k)


def mean_completion_time(C: np.ndarray, model, k: int, *, trials: int = 10000,
                         seed: int = 0, chunk: int | None = None) -> float:
    """Paper eq. (5): average completion time of schedule C."""
    n = np.asarray(C).shape[0]
    res = montecarlo.sweep([montecarlo.to_spec("to", C)], model, n,
                           trials=trials, seed=seed, chunk=chunk, ks=k)
    return res.at_k("to", k)
