"""Training substrate tests: optimizers, straggler-scheduled step (eq. 61),
data pipeline determinism, checkpointing."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (RoundConfig, scenario1, cyclic_to_matrix, ec2_cluster,
                        greedy_row_assignment)
from repro.data import TaskPartition, lm_task_batches, bigram_tokens
from repro.models import ModelConfig, init_cache
from repro.optim import (adamw, sgd, momentum, cosine_schedule,
                         clip_by_global_norm, global_norm)
from repro.train import (init_train_state, make_train_step,
                         make_straggler_train_step, make_serve_step, lm_loss)

CFG = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                  param_dtype="float32", dtype="float32", remat=False)


class TestOptimizers:
    def _quad(self, opt, steps=60):
        """Minimize ||x - 3||^2 with each optimizer."""
        params = {"x": jnp.zeros((4,))}
        state = opt.init(params)
        for _ in range(steps):
            g = {"x": 2 * (params["x"] - 3.0)}
            upd, state = opt.update(g, state, params)
            params = opt.apply(params, upd)
        return float(jnp.abs(params["x"] - 3.0).max())

    def test_sgd(self):
        assert self._quad(sgd(0.1)) < 1e-3

    def test_momentum(self):
        assert self._quad(momentum(0.02), steps=200) < 1e-2

    def test_adamw_no_decay(self):
        assert self._quad(adamw(0.3, weight_decay=0.0), steps=200) < 1e-2

    def test_cosine_schedule(self):
        s = cosine_schedule(1.0, 100, warmup=10)
        assert float(s(jnp.asarray(0))) == 0.0
        assert abs(float(s(jnp.asarray(10))) - 1.0) < 1e-6
        assert float(s(jnp.asarray(100))) < 1e-6

    def test_clip(self):
        tree = {"a": jnp.full((10,), 10.0)}
        clipped, norm = clip_by_global_norm(tree, 1.0)
        assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
        assert float(norm) > 1.0


class TestStragglerStep:
    def test_loss_decreases_and_metrics(self):
        opt = adamw(1e-2, weight_decay=0.0)
        state = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        spec = RoundConfig(n=4, r=2, k=3, kind="cs")
        part = TaskPartition(n=4, global_batch=8, seq_len=16,
                             vocab=64, source="bigram")
        step = jax.jit(make_straggler_train_step(CFG, opt, spec, scenario1()))
        C = spec.to_matrix()
        first = last = None
        for i in range(40):
            toks, labs = lm_task_batches(part, C, i)
            state, m, _ = step(state, toks, labs, jax.random.PRNGKey(i))
            if first is None:
                first = float(m["loss"])
            last = float(m["loss"])
            assert int(m["winners"]) == 3
            assert float(m["completion_time"]) > 0
        assert last < first - 0.3, (first, last)

    def test_k_equals_n_uses_all_tasks(self):
        opt = sgd(1e-2)
        state = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        spec = RoundConfig(n=4, r=2, k=4, kind="ss")
        part = TaskPartition(n=4, global_batch=4, seq_len=8, vocab=64)
        step = jax.jit(make_straggler_train_step(CFG, opt, spec, scenario1()))
        toks, labs = lm_task_batches(part, spec.to_matrix(), 0)
        state, m, _ = step(state, toks, labs, jax.random.PRNGKey(0))
        assert int(m["winners"]) == 4

    def test_equals_plain_step_when_k_n_r1(self):
        """r=1, k=n: every task used exactly once -> gradient equals the
        plain full-batch step (same data, same init)."""
        opt = sgd(0.1)
        spec = RoundConfig(n=4, r=1, k=4, kind="cs")
        part = TaskPartition(n=4, global_batch=4, seq_len=8, vocab=64)
        C = spec.to_matrix()
        toks, labs = lm_task_batches(part, C, 0)

        s1 = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        stepA = jax.jit(make_straggler_train_step(CFG, opt, spec,
                                                  scenario1(),
                                                  clip_norm=1e9))
        s1, mA, _ = stepA(s1, toks, labs, jax.random.PRNGKey(5))

        # plain step on the same data: tasks stacked into one batch.
        # C is cyclic with r=1 -> worker i computes task i, slot 0.
        flat_t = toks[0].reshape(-1, toks.shape[-1])
        flat_l = labs[0].reshape(-1, labs.shape[-1])
        s2 = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        stepB = jax.jit(make_train_step(CFG, opt, clip_norm=1e9))
        s2, mB = stepB(s2, flat_t, flat_l)

        for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                        jax.tree_util.tree_leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-6)

    def test_cluster_state_threads_through_steps(self):
        """Round-aware training: the DelayProcess state returned by one
        step feeds the next, and with near-frozen stragglers the observed
        per-worker delays stay correlated across consecutive rounds."""
        opt = sgd(1e-2)
        state = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        spec = RoundConfig(n=4, r=2, k=3, kind="cs")
        proc = ec2_cluster(4, spread=2.0, p_slow=0.5, persistence=0.98,
                           slow=50.0)
        part = TaskPartition(n=4, global_batch=8, seq_len=16, vocab=64)
        step = jax.jit(make_straggler_train_step(CFG, opt, spec, proc))
        C = spec.to_matrix()
        cluster = None
        t1s = []
        for i in range(8):
            toks, labs = lm_task_batches(part, C, i)
            state, m, cluster = step(state, toks, labs,
                                     jax.random.PRNGKey(i), cluster)
            assert m["worker_t1"].shape == (4,)
            t1s.append(np.asarray(m["worker_t1"]))
        assert cluster is not None and np.asarray(cluster).shape == (1, 4)
        t1s = np.stack(t1s)                     # (rounds, n)
        # a worker slowed 50x stays slow: per-round worker ranking is
        # essentially constant under persistence=0.98
        ranks = np.argsort(np.argsort(t1s, axis=1), axis=1)
        assert (ranks.std(axis=0).mean()) < 1.0

    def test_row_permutation_matches_identity_when_trivial(self):
        """Passing row_of_worker=arange must reproduce the static path
        exactly; a nontrivial permutation with matching data keeps the
        winner count at k."""
        opt = sgd(1e-2)
        spec = RoundConfig(n=4, r=2, k=3, kind="cs")
        part = TaskPartition(n=4, global_batch=8, seq_len=16, vocab=64)
        step = jax.jit(make_straggler_train_step(CFG, opt, spec, scenario1()))
        C = spec.to_matrix()
        toks, labs = lm_task_batches(part, C, 0)
        s0 = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        _, mA, _ = step(s0, toks, labs, jax.random.PRNGKey(7))
        _, mB, _ = step(s0, toks, labs, jax.random.PRNGKey(7), None,
                        jnp.arange(4))
        assert float(mA["completion_time"]) == float(mB["completion_time"])
        assert float(mA["loss"]) == float(mB["loss"])
        # nontrivial permutation: effective schedule rows permuted, data
        # built from the effective matrix
        row = np.array([2, 3, 0, 1])
        toks2, labs2 = lm_task_batches(part, C[row], 0)
        _, mC, _ = step(s0, toks2, labs2, jax.random.PRNGKey(7), None,
                        jnp.asarray(row))
        assert int(mC["winners"]) == 3
        assert float(mC["completion_time"]) > 0

    def test_unbiasedness_scaling(self):
        """eq. (61): with k < n the estimator scales by n/k — the expected
        gradient over delay randomness equals the full-data gradient.
        Verified by averaging the weighted loss value over many rounds."""
        spec = RoundConfig(n=6, r=6, k=3, kind="cs")
        part = TaskPartition(n=6, global_batch=6, seq_len=8, vocab=64)
        C = spec.to_matrix()
        toks, labs = lm_task_batches(part, C, 0)
        opt = sgd(0.0)  # no movement; probe loss only
        state = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        step = jax.jit(make_straggler_train_step(CFG, opt, spec, scenario1()))
        vals = []
        for i in range(48):
            _, m, _c = step(state, toks, labs, jax.random.PRNGKey(i))
            vals.append(float(m["loss"]))
        # full-data mean loss over the 6 distinct tasks
        full = 0.0
        for j in range(6):
            l, _ = lm_loss(state.params, CFG, toks[0, j], labs[0, j])
            full += float(l) / 6
        est = np.mean(vals)
        assert abs(est - full) / full < 0.05, (est, full)


class TestData:
    def test_task_batches_shapes_and_determinism(self):
        part = TaskPartition(n=4, global_batch=8, seq_len=16, vocab=64)
        C = cyclic_to_matrix(4, 2)
        t1, l1 = lm_task_batches(part, C, step=3)
        t2, l2 = lm_task_batches(part, C, step=3)
        assert t1.shape == (2, 4, 2, 16)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
        # labels are inputs shifted by one
        np.testing.assert_array_equal(np.asarray(t1)[..., 1:],
                                      np.asarray(l1)[..., :-1])

    def test_redundant_tasks_identical_across_workers(self):
        """Two workers assigned the same task see identical data."""
        part = TaskPartition(n=4, global_batch=8, seq_len=8, vocab=64)
        C = cyclic_to_matrix(4, 3)   # task 2 at (0,2), (1,1), (2,0)
        t, _ = lm_task_batches(part, C, step=0)
        np.testing.assert_array_equal(np.asarray(t[2, 0]),
                                      np.asarray(t[1, 1]))
        np.testing.assert_array_equal(np.asarray(t[1, 1]),
                                      np.asarray(t[0, 2]))

    def test_bigram_is_learnable_structure(self):
        toks = bigram_tokens(jax.random.PRNGKey(0), 64, 32, 16)
        a = np.asarray(toks)
        # bigram chain: distribution of next token given current is peaked
        joint = np.zeros((16, 16))
        for row in a:
            for x, y in zip(row[:-1], row[1:]):
                joint[x, y] += 1
        cond = joint / np.maximum(joint.sum(1, keepdims=True), 1)
        assert (cond.max(1) > 0.3).mean() > 0.5


class TestCheckpoint:
    def test_roundtrip_train_state(self):
        from repro.ckpt import (save_checkpoint, load_checkpoint,
                                latest_checkpoint)
        opt = adamw(1e-3)
        state = init_train_state(jax.random.PRNGKey(0), CFG, opt)
        with tempfile.TemporaryDirectory() as d:
            path = save_checkpoint(os.path.join(d, "ck"), state, step=17)
            restored = load_checkpoint(path, state)
            for a, b in zip(jax.tree_util.tree_leaves(restored),
                            jax.tree_util.tree_leaves(state)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert latest_checkpoint(d, "ck").endswith("ck-00000017.npz")

    def test_shape_mismatch_raises(self):
        from repro.ckpt import save_checkpoint, load_checkpoint
        with tempfile.TemporaryDirectory() as d:
            p = save_checkpoint(os.path.join(d, "x"), {"a": jnp.ones((3,))})
            with pytest.raises(ValueError):
                load_checkpoint(p, {"a": jnp.ones((4,))})


def test_serve_step_greedy_deterministic():
    opt = sgd(0.0)
    state = init_train_state(jax.random.PRNGKey(0), CFG, opt)
    serve = jax.jit(make_serve_step(CFG))
    c1 = init_cache(CFG, 1, 16)
    c2 = init_cache(CFG, 1, 16)
    t1 = t2 = jnp.zeros((1, 1), jnp.int32)
    for _ in range(5):
        t1, c1 = serve(state.params, c1, t1)
        t2, c2 = serve(state.params, c2, t2)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
