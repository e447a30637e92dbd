"""Train / serve steps.

``make_straggler_train_step`` is the paper's technique as a first-class
feature: one SGD iteration = one scheduling round. The n logical workers
(data-parallel shard groups) each evaluate their r TO-assigned micro-batch
gradients *sequentially* (lax.scan over slots, mirroring the paper's
sequential computation); the first-k-distinct winner mask (repro.core)
weights the per-(worker, slot) losses so the resulting gradient equals the
unbiased eq.-(61) estimator. The round's virtual completion time is a step
metric.

Round-awareness: delays come from a stateful ``DelayProcess``
(``repro.core.cluster``) whose per-worker straggler state threads through
the step as an explicit ``cluster`` pytree — pass each step's returned
cluster state into the next step and consecutive rounds see persistent,
worker-specific straggling (stateless ``DelayModel``s remain the
zero-correlation special case with an empty state).  An optional traced
``row_of_worker`` permutation re-assigns the base TO matrix's rows to
workers for the round (the adaptive schedule; see
``repro.core.scheduling.AdaptiveScheduler``) — the caller must build the
round's data with the matching effective matrix ``C[row_of_worker]``.

The weighted-loss trick avoids materializing per-worker gradient pytrees:
    grad( sum_{i,s} w[i,s] * loss_{i,s} / k ) = (1/k) sum w[i,s] g_{i,s}.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.cluster import as_process
from ..core.round import make_round_close
from ..core.spec import RoundConfig
from ..models import ModelConfig, forward, init_params
from ..optim import Optimizer, clip_by_global_norm
from ..sharding import DATA, shard

PyTree = Any


@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt_state: PyTree
    step: jax.Array

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def init_train_state(key, cfg: ModelConfig, opt: Optimizer) -> TrainState:
    params = init_params(key, cfg)
    return TrainState(params=params, opt_state=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def lm_loss_per_seq(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
                    labels: jax.Array, *, embeds=None, enc_frames=None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Per-sequence next-token cross-entropy (B,); returns (losses, aux)."""
    logits, aux, _ = forward(params, cfg, tokens, embeds=embeds,
                             enc_frames=enc_frames)
    if embeds is not None:
        logits = logits[:, embeds.shape[1]:]      # loss on text positions
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    return -ll.mean(axis=-1), aux


def lm_loss(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, *, embeds=None, enc_frames=None
            ) -> Tuple[jax.Array, jax.Array]:
    """Mean next-token cross-entropy; returns (loss, moe_aux)."""
    losses, aux = lm_loss_per_seq(params, cfg, tokens, labels,
                                  embeds=embeds, enc_frames=enc_frames)
    return losses.mean(), aux


def make_train_step(cfg: ModelConfig, opt: Optimizer, *,
                    clip_norm: float = 1.0,
                    loss_fn: Optional[Callable] = None):
    """Plain synchronous data-parallel step (baseline, k = n, r = 1)."""
    loss_fn = loss_fn or lm_loss

    def step(state: TrainState, tokens, labels, extras=None):
        extras = extras or {}

        def total(p):
            l, aux = loss_fn(p, cfg, tokens, labels, **extras)
            return l + cfg.router_aux_coef * aux, (l, aux)

        (ltot, (l, aux)), grads = jax.value_and_grad(total, has_aux=True)(
            state.params)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = opt.apply(state.params, updates)
        metrics = {"loss": l, "aux": aux, "grad_norm": gnorm}
        return TrainState(params, opt_state, state.step + 1), metrics

    return step


def make_straggler_train_step(cfg: ModelConfig, opt: Optimizer,
                              round_config: RoundConfig, delay, *,
                              clip_norm: float = 1.0,
                              scan_slots: bool = True):
    """The paper's scheduled round as a jittable SGD step.

    Inputs per step: ``slot_tokens``/``slot_labels`` (r, n, b, S) from
    ``repro.data.lm_task_batches``, an rng for the delay realization, the
    previous round's ``cluster`` state (``None`` starts a fresh cluster;
    pass the returned state back in for persistent straggling), optionally
    a traced ``row_of_worker`` permutation (adaptive schedules; data must
    then come from the effective matrix ``C[row_of_worker]``), and
    optionally ``extras`` (dict of slot-major modality inputs, e.g.
    ``enc_frames`` (r, n, b, T_enc, D) for whisper). Returns
    ``(state, metrics, cluster)`` with metrics incl. the round's virtual
    completion time (eq. 6), the winner count, the per-worker observed
    compute delays (``worker_t1``) that feed adaptive scheduling, and the
    raw per-(worker, slot) delay draws (``slot_t1``/``slot_t2``) that
    ``launch/train.py --log-delays`` accumulates into a replayable
    ``DelayTrace``.

    Layout: the worker axis is FLATTENED into the batch (worker-major), so
    each data shard holds exactly its workers' sequences and the model
    forward is one plain SPMD call per slot — per-sequence losses are then
    weighted by the worker's first-k-distinct mask (eq. 61). ``scan_slots``
    mirrors the paper's sequential per-slot execution; set False to unroll
    (used by the dry-run for exact HLO cost accounting).

    The round itself is ``repro.core.round``'s round close: ragged
    ``loads`` give masked slots +inf arrivals, zero winner weight and
    all-zero micro-batches from ``lm_task_batches``; ``comm_eps`` adds the
    per-message protocol overhead to every arrival.  Load re-balancing
    (``rebalance``) changes the slot layout every round, which the step's
    static (r, n) batches cannot follow, so it is refused.
    """
    if round_config.rebalance:
        raise ValueError("make_straggler_train_step cannot run rebalance: "
                         "its slot batches have a static per-row layout")
    n, r = round_config.n, round_config.width
    process = as_process(delay)
    close = make_round_close(round_config)
    loads_w = round_config.load_vector

    def step(state: TrainState, slot_tokens, slot_labels, rng, cluster=None,
             row_of_worker=None, extras=None):
        extras = extras or {}
        b = slot_tokens.shape[2]
        # --- cluster round: stateful delays + first-k-distinct weights ----
        if cluster is None:
            # trial id 0: a training run is the single realization of a
            # trace-backed process (lane 0 of its recorded table)
            cluster = process.init_trials(
                jax.random.fold_in(rng, 0x0c10)[None],
                jnp.zeros((1,), jnp.int32), n)
        cluster, T1, T2 = process.step(cluster, rng[None], n, r)
        if row_of_worker is None:
            row_of_worker = jnp.arange(n)
        rc = close(T1, T2, row_of_worker, loads_w)
        weights = rc.weights

        # realized selected-task count: == k a.s. with per-slot sends, may
        # exceed k when a reduced message budget delivers tasks in lumps —
        # or fall short (even to 0) when faults/deadlines censor arrivals;
        # guard the normalizer so an empty round yields a zero gradient,
        # not NaN.
        wsum_raw = weights.sum()
        wsum = jnp.where(wsum_raw > 0, wsum_raw, 1.0)

        def slot_loss(p, s):
            toks = slot_tokens[s].reshape(n * b, -1)         # worker-major
            labs = slot_labels[s].reshape(n * b, -1)
            toks = shard(toks, DATA, None, note="slot.tokens")
            kw = {key: v[s].reshape((n * b,) + v.shape[3:])
                  for key, v in extras.items()}
            losses, aux = lm_loss_per_seq(p, cfg, toks, labs, **kw)
            w_seq = jnp.repeat(weights[:, s], b) / (wsum * b)  # eq. (61)
            return (w_seq * losses).sum(), aux * (weights[:, s].sum() / wsum)

        def total(p):
            if scan_slots:
                def slot_term(carry, s):
                    l, a = slot_loss(p, s)
                    return (carry[0] + l, carry[1] + a), None
                (loss, aux), _ = jax.lax.scan(
                    slot_term, (jnp.zeros(()), jnp.zeros(())),
                    jnp.arange(r))
            else:
                loss = aux = jnp.zeros(())
                for s in range(r):
                    l, a = slot_loss(p, s)
                    loss, aux = loss + l, aux + a
            return loss + cfg.router_aux_coef * aux, (loss, aux)

        (ltot, (l, aux)), grads = jax.value_and_grad(total, has_aux=True)(
            state.params)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = opt.apply(state.params, updates)
        metrics = {"loss": l, "aux": aux, "grad_norm": gnorm,
                   "completion_time": rc.t_eff,
                   "winners": (weights > 0).sum(),
                   "realized_k": wsum_raw,
                   "delivered_tasks": rc.delivered,
                   "deadline_missed": rc.missed,
                   "worker_t1": T1[0].mean(axis=-1),
                   # raw per-(worker, slot) delay draws of the round —
                   # what `launch/train.py --log-delays` accumulates into
                   # a replayable DelayTrace (repro.core.trace)
                   "slot_t1": T1[0], "slot_t2": T2[0]}
        return TrainState(params, opt_state, state.step + 1), metrics, cluster

    return step


def make_serve_step(cfg: ModelConfig, *, greedy: bool = True):
    """One decode step: (params, cache, tokens (B,1)) -> (next (B,1), cache).
    """
    def step(params, cache, tokens, rng=None):
        logits, _, cache = forward(params, cfg, tokens, cache=cache)
        last = logits[:, -1]
        if greedy or rng is None:
            nxt = jnp.argmax(last, axis=-1)
        else:
            nxt = jax.random.categorical(rng, last)
        return nxt[:, None].astype(jnp.int32), cache

    return step
