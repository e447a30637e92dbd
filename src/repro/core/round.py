"""One scheduling round, written once: the round close and the scheduler
that every consumer outside the Monte-Carlo engine calls.

``make_round_close(cfg)`` builds the traceable close of one round from a
``RoundConfig``: slot arrivals (eq. 1), the round's row permutation, the
static per-row message layout (or, under ``rebalance``, the per-round load
mask and closing-slot remap), per-task arrivals through the static gather
plan (eq. 2), the k-th order statistic (eq. 6) and the deadline policy.
The aggregator, the straggler train step and the live master call it; the
engine's batched rounds scan (``montecarlo._build_rounds_fn``) keeps its
own body for speed and shares ``policy_close`` with it, and a parity test
ties the two together.

The close works on one round's machine-major tables at the engine's
``(1, n, r)`` chunk shape, so eq. 1 is the same XLA arithmetic as
``sweep_rounds(..., trials=1)`` and a recorded live trace replays through
the engine bit for bit.  It is not jitted: the train step traces it inside
its own step, the aggregator and the live master jit it once each.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import scheduling
from .completion import (_static_active, _winner_weights, apply_row_layout,
                         message_slot_layout, row_layout_is_identity)
from .montecarlo import (_kth_smallest, _rebalance_remap_table,
                         task_arrival_times_gather, task_gather_plan)
from .spec import RoundConfig

__all__ = ["RoundClose", "make_round_close", "make_scheduler",
           "policy_close"]

Array = jax.Array


class RoundClose(NamedTuple):
    """What one closed round hands its consumer."""
    weights: Array     # (n, r) worker-major eq.-61 winner weights
    t_eff: Array       # () effective completion (deadline-capped)
    tau: Array         # (n,) per-task arrivals (eq. 2)
    delivered: Array   # (n,) bool: task delivered by the round close
    arr_w: Array       # (n, r) worker-major per-message arrivals
    realized: Array    # () float32 distinct results counted, at most k
    missed: Array      # () bool: the round blew its deadline


def policy_close(v, by, dv, *, k, deadline, policy: str):
    """Apply the deadline fallback policy to a raw completion ``v`` given
    its arrival counts (``by`` distinct results by the deadline, ``dv``
    ever delivered): returns ``(v_eff, realized, missed)``, ``missed`` as
    float32.  ``wait`` reports the true completion and flags a miss;
    ``close_partial`` and ``reissue`` close at ``min(v, deadline)`` with
    what arrived by then.  Shared by the round close and the engine's
    rounds scan."""
    if policy == "wait":
        return v, jnp.minimum(dv, k), (~(v <= deadline)).astype(jnp.float32)
    return (jnp.minimum(v, deadline), jnp.minimum(by, k),
            (by < k).astype(jnp.float32))


def make_round_close(cfg: RoundConfig):
    """The traceable ``close(T1, T2, row_of_worker, loads_w) -> RoundClose``
    for rounds under ``cfg``.

    ``T1``/``T2`` are the round's (1, n, r) machine-major compute and
    communication delays (+inf where nothing arrived); row ``p`` of the
    base matrix is executed by the worker ``w`` with ``row_of_worker[w] ==
    p`` (``arange(n)`` for a static schedule); ``loads_w`` is each worker's
    load this round, read only under ``rebalance``."""
    n, r, k = cfg.n, cfg.width, cfg.k
    # rebalance masks slots per round, so its base is the dense cap grid;
    # otherwise the (possibly ragged) schedule bakes its masks in.
    base_C = cfg.base_matrix() if cfg.rebalance else cfg.to_matrix()
    plan = task_gather_plan(base_C, n)
    active = _static_active(base_C)
    layout = remap = None
    if cfg.rebalance:
        remap = _rebalance_remap_table(r, cfg.n_messages)
    else:
        layout = message_slot_layout(scheduling.loads_of_matrix(base_C), r,
                                     cfg.n_messages, cfg.comm_eps)
        if row_layout_is_identity(layout):
            layout = None
    DL = None if cfg.deadline is None else np.float32(cfg.deadline)
    kf = np.float32(k)

    def close(T1, T2, row_of_worker, loads_w) -> RoundClose:
        s = (jnp.cumsum(T1, axis=-1) + T2)[0]            # eq. (1)
        worker_of_row = jnp.argsort(row_of_worker)
        s2 = s[worker_of_row]                            # row-major arrivals
        # the message grouping follows the row a worker executes
        if layout is not None:
            s2 = apply_row_layout(s2, layout)
        if cfg.rebalance:
            l_row = loads_w[worker_of_row]
            s2 = jnp.where(jnp.arange(r)[None, :] < l_row[:, None], s2,
                           jnp.inf)
            if remap is not None:
                mm = jnp.take(jnp.asarray(remap), l_row - 1, axis=0)
                s2 = jnp.take_along_axis(s2, mm, axis=-1)
        tau = task_arrival_times_gather(plan, s2)        # eq. (2)
        v = _kth_smallest(tau, k)[0]                     # eq. (6)
        dv = jnp.isfinite(tau).sum(-1).astype(jnp.float32)
        if DL is None:
            t_eff, realized, missed = v, jnp.minimum(dv, kf), jnp.float32(0)
        else:
            by = (tau <= DL).sum(-1).astype(jnp.float32)
            t_eff, realized, missed = policy_close(
                v, by, dv, k=kf, deadline=DL, policy=cfg.deadline_policy)
        w2 = _winner_weights(jnp.asarray(base_C), s2, tau, t_eff, active)
        delivered = (tau <= t_eff) & jnp.isfinite(tau)
        return RoundClose(weights=w2[row_of_worker], t_eff=t_eff, tau=tau,
                          delivered=delivered, arr_w=s2[row_of_worker],
                          realized=realized, missed=missed > 0)

    return close


def make_scheduler(cfg: RoundConfig
                   ) -> Optional[scheduling.AdaptiveScheduler]:
    """The adaptive scheduler ``cfg`` asks for, or None for a static
    schedule."""
    if not cfg.adaptive:
        return None
    kw = dict(beta=cfg.feedback_beta, gamma=cfg.coverage_gamma)
    if cfg.dead_after is not None:
        kw.update(dead_after=cfg.dead_after, target_k=cfg.k)
    if cfg.rebalance:
        return scheduling.AdaptiveScheduler(
            cfg.base_matrix(), loads=cfg.loads, rebalance=True, **kw)
    return scheduling.AdaptiveScheduler(cfg.to_matrix(), **kw)
