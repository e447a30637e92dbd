"""First-k-distinct gradient aggregation (paper eq. 61) as a reusable JAX
module — the bridge between the paper's scheduling theory and the training
framework.

One SGD iteration = one *round*:

  1. the global batch is split into ``n`` logical tasks (micro-batches);
  2. worker ``i`` (a data-parallel shard group) evaluates the gradients of
     tasks ``C[i, 0..r-1]`` sequentially;
  3. the round's delay realization comes from a stateful ``DelayProcess``
     (``repro.core.cluster``): worker-specific straggling *persists* across
     ``round_mask`` calls, so consecutive rounds see correlated delays just
     like a real cluster (stateless ``DelayModel``s are coerced to the
     zero-correlation ``IIDProcess``; a recorded ``DelayTrace`` replays a
     *measured* cluster through the same API — see ``repro.core.trace``);
  4. the earliest copies of the k earliest distinct tasks are combined with
     the unbiased scaling of eq. (61):

         theta <- theta - eta * (n / k) * sum_{selected tasks} g_task

     (the n/k factor is folded into the returned gradient).

The round is described by one ``RoundConfig``.  With ``adaptive=True``
the aggregator re-permutes the base TO matrix's rows every round from
observed per-worker delay feedback (greedy least-covered-first,
``repro.core.scheduling.AdaptiveScheduler``): fetch the effective schedule
for the coming round with ``current_matrix()`` *before* calling
``round_mask`` (it decides which task's data each worker loads).
``censored_feedback`` restricts that feedback to messages that reached
the master before the round completed (what a real master observes), and
``messages`` sets the per-round message budget (paper Sec. V-C): results
become available in per-message lumps instead of per slot.

Ragged rounds: ``loads`` gives each TO-matrix row its own load (trailing
slots ``MASKED``; winner weights there are identically zero and eq. (61)
normalizes by the realized selected count), and ``rebalance`` additionally
re-allocates whole slots between workers each round from the same
feedback (``greedy_load_rebalance`` under the fixed total budget
``sum(loads)``, per-worker cap ``r``) — fetch ``current_loads()`` /
``current_matrix()`` before each round.  ``comm_eps`` adds the serialized
per-message protocol overhead (Ozfatura et al.'s communication/computation
trade-off).

The selection mask is a deterministic function of the arrival times and is
computed identically on every shard (cheap: n*r scalars), keeping the whole
round a single SPMD step.  The round itself is ``repro.core.round``'s round
close, and ``expected_completion`` delegates to the engine's
``sweep_rounds`` — there is no separate simulation code path here.
"""
from __future__ import annotations

import zlib
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import montecarlo
from .cluster import IIDProcess, as_process
from .round import make_round_close, make_scheduler
from .spec import RoundConfig

__all__ = ["StragglerAggregator"]

Array = jax.Array
PyTree = Any


def _seed_of(key) -> int:
    """Accept an int seed or a PRNG key — raw uint32 or new-style typed —
    (compat with the pre-round API, which took a key).  The whole key
    feeds the seed so distinct keys give distinct MC streams."""
    if key is None:
        return 0
    if isinstance(key, (int, np.integer)):
        return int(key)
    try:
        data = np.asarray(jax.random.key_data(key))
    except TypeError:
        data = np.asarray(key)
    if data.ndim == 0:
        return int(data)
    return zlib.crc32(np.ascontiguousarray(data).tobytes()) & 0x7FFFFFFF


class StragglerAggregator:
    """Combines per-(worker, slot) gradients into the eq.-(61) estimate,
    holding the cluster's straggler state across rounds.

    Usage inside a train loop::

        agg = StragglerAggregator(RoundConfig(n=16, k=12, kind="ss", r=2),
                                  ec2_cluster(16, persistence=0.95))
        for step in range(steps):
            C = agg.current_matrix()                 # schedule this round
            ...load each worker's task data from C...
            weights, t_done = agg.round_mask(rng)    # (n, r) weights, scalar
            grad = agg.combine(slot_grads, weights)  # pytree

    ``slot_grads`` is a pytree whose leaves have leading dims (n, r) — the
    gradient of task C[i, j] computed by worker i at slot j (already averaged
    within the micro-batch).
    """

    def __init__(self, cfg: RoundConfig, delay, *,
                 init_key: Array | None = None):
        self.cfg = cfg
        self.process = as_process(delay)
        self.scheduler = make_scheduler(cfg)
        self._close = make_round_close(cfg)
        if init_key is None:
            init_key = jax.random.PRNGKey(cfg.seed)
        # trial id 0: a live training run is the single realization of a
        # trace-backed process (lane 0 of its table); parametric processes
        # ignore the id.
        self._state = self.process.init_trials(
            init_key[None], jnp.zeros((1,), jnp.int32), cfg.n)
        self._rounds_done = 0
        self.rounds_missed = 0          # rounds that blew the deadline
        self.realized_k_history: list[float] = []   # realized count / round
        self._round = jax.jit(self._round_fn)

    # --- one round, jitted: the cluster's delays, then the round close -----
    def _round_fn(self, state, keys, row_of_worker, loads_w):
        state, T1, T2 = self.process.step(state, keys, self.cfg.n,
                                          self.cfg.width)
        return state, T1[0], self._close(T1, T2, row_of_worker, loads_w)

    def current_matrix(self) -> np.ndarray:
        """The effective TO matrix for the coming round (row ``w`` = tasks
        worker ``w`` executes; ``MASKED`` beyond worker ``w``'s load).
        Static schedules return the base matrix; adaptive ones the
        feedback-driven row re-assignment (and load re-balance)."""
        if self.scheduler is None:
            return self.cfg.to_matrix()
        return self.scheduler.matrix()

    def current_loads(self) -> np.ndarray:
        """Per-worker loads for the coming round (matches
        ``current_matrix()``'s active slots)."""
        if self.scheduler is None:
            return self.cfg.load_vector
        return self.scheduler.loads()

    def round_mask(self, key: Array) -> Tuple[Array, Array]:
        """Advance the cluster one round, returning (weights (n, r),
        completion time scalar). weights[i, j] in [0, 1]; sums to the
        *realized* distinct-result count over all slots (k almost surely
        without faults/deadlines) and matches ``current_matrix()``'s
        worker/slot layout."""
        # finite sources (trace replay) enforce their horizon policy here:
        # the live loop learns it ran off the recording's end *before* the
        # round executes, with the remedy in the error message.
        self.process.check_rounds(self._rounds_done + 1)
        cfg = self.cfg
        row_of_worker = (np.arange(cfg.n) if self.scheduler is None
                         else self.scheduler.row_of_worker())
        loads_w = (self.scheduler.loads() if cfg.rebalance
                   else cfg.load_vector)
        self._state, t1, rc = self._round(
            self._state, key[None], jnp.asarray(row_of_worker),
            jnp.asarray(loads_w))
        self._rounds_done += 1
        self.realized_k_history.append(float(np.asarray(rc.weights).sum()))
        self.rounds_missed += int(rc.missed)
        if self.scheduler is not None:
            if cfg.censored_feedback:
                # a real master only sees messages that beat the round close
                self.scheduler.observe(np.asarray(t1),
                                       arrivals=np.asarray(rc.arr_w),
                                       t_done=float(rc.t_eff))
            else:
                self.scheduler.observe(np.asarray(t1))
            if cfg.deadline_policy == "reissue":
                # undelivered tasks get re-gather priority next round
                self.scheduler.set_need(~np.asarray(rc.delivered))
        return rc.weights, rc.t_eff

    def combine(self, slot_grads: PyTree, weights: Array) -> PyTree:
        """eq. (61): grad = (n/k) * mean over selected tasks of task grads
        == (1/k) * sum selected (if task grads are already per-task means,
        the global-batch-equivalent estimate is sum * n/k / n = sum/k).

        Normalized by the *realized* selected-task count (``weights.sum()``):
        with per-slot sends that is k almost surely (eq. 61 exactly), but a
        reduced message budget makes arrival ties structural — a message can
        deliver more distinct tasks than the target still missing — and the
        unbiased scaling then divides by however many arrived.  A round
        that realized *nothing* (every arrival fault-censored past the
        deadline) yields a zero gradient instead of 0/0 NaN."""
        den_raw = weights.sum()
        den = jnp.where(den_raw > 0, den_raw, 1.0)

        def _one(g):
            w = weights.reshape(weights.shape + (1,) * (g.ndim - 2))
            return (g * w).sum(axis=(0, 1)) / den
        return jax.tree_util.tree_map(_one, slot_grads)

    def expected_completion(self, key: Array | int = 0, trials: int = 4096,
                            rounds: int | None = None) -> float:
        """MC estimate of the average per-round completion time (eq. 5),
        via the fused engine.  For stateful processes the estimate scans
        ``rounds`` consecutive rounds (default 8) and averages; for the
        i.i.d. shim one round suffices.  ``key`` may be an int seed or a
        PRNG key (compat)."""
        from .trace import TraceProcess
        if rounds is None:
            rounds = 1 if isinstance(self.process, IIDProcess) else 8
            if isinstance(self.process, TraceProcess):
                # a strict trace can only serve what remains of its
                # recorded horizon after the replay offset
                rounds = min(rounds, self.process.trace.rounds
                             - int(self.process.start_round))
        res = montecarlo.sweep_rounds(
            [self.cfg.to_scheme_spec("s")], self.process, self.cfg.n,
            rounds=rounds, trials=trials, seed=_seed_of(key),
            **self.cfg.sweep_rounds_kwargs())
        return res.mean_round("s")
