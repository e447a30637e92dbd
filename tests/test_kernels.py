"""Per-kernel interpret-mode validation against the pure-jnp oracles:
shape/dtype sweeps + hypothesis property tests (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.ops import (gram_matvec, batched_gram_matvec,
                               greedy_assign, swa_attention)
from repro.core.scheduling import (cyclic_to_matrix,
                                   greedy_row_assignment_batch,
                                   random_assignment_to_matrix,
                                   staircase_to_matrix)


class TestGramMatvec:
    @pytest.mark.parametrize("d,b", [(64, 32), (128, 128), (300, 200),
                                     (100, 300), (512, 64), (37, 53)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, d, b, dtype):
        key = jax.random.PRNGKey(d * 1000 + b)
        X = jax.random.normal(key, (d, b), dtype)
        th = jax.random.normal(jax.random.PRNGKey(7), (d,), dtype)
        out = gram_matvec(X, th)
        want = ref.gram_matvec_ref(X, th)
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        rel = (np.abs(np.asarray(out, np.float32) -
                      np.asarray(want, np.float32)).max()
               / (np.abs(np.asarray(want, np.float32)).max() + 1e-9))
        assert rel < tol, rel
        assert out.dtype == X.dtype

    def test_block_sizes(self):
        X = jax.random.normal(jax.random.PRNGKey(0), (384, 256))
        th = jax.random.normal(jax.random.PRNGKey(1), (384,))
        want = np.asarray(ref.gram_matvec_ref(X, th))
        for bd, bb in [(128, 128), (256, 64), (384, 256), (64, 256)]:
            out = np.asarray(gram_matvec(X, th, block_d=bd, block_b=bb))
            np.testing.assert_allclose(out, want, rtol=1e-5, atol=2e-3)

    def test_batched_matches_paper_gradient_piece(self):
        """sum_i h(X_i) must equal X^T X theta (paper eq. 48)."""
        n, d, b = 4, 96, 48
        Xs = jax.random.normal(jax.random.PRNGKey(0), (n, d, b))
        th = jax.random.normal(jax.random.PRNGKey(1), (d,))
        hs = batched_gram_matvec(Xs, th)
        assert hs.shape == (n, d)
        Xflat = np.concatenate([np.asarray(Xs[i]) for i in range(n)], axis=1)
        want = Xflat @ (Xflat.T @ np.asarray(th))
        np.testing.assert_allclose(np.asarray(hs.sum(0)), want,
                                   rtol=1e-4, atol=1e-3)

    @settings(deadline=None, max_examples=20)
    @given(st.integers(8, 200), st.integers(8, 200), st.integers(0, 2**16))
    def test_property_matches_oracle(self, d, b, seed):
        X = jax.random.normal(jax.random.PRNGKey(seed), (d, b))
        th = jax.random.normal(jax.random.PRNGKey(seed + 1), (d,))
        out = np.asarray(gram_matvec(X, th, block_d=64, block_b=64))
        want = np.asarray(ref.gram_matvec_ref(X, th))
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-4)


class TestSWAAttention:
    @pytest.mark.parametrize("T,H,dh,W", [
        (128, 2, 64, 32), (200, 1, 32, 64), (256, 2, 128, 100),
        (64, 4, 16, 8), (96, 1, 64, 96),      # window == seq (full causal)
        (130, 2, 32, 17),                      # odd sizes
    ])
    def test_shapes(self, T, H, dh, W):
        q = jax.random.normal(jax.random.PRNGKey(0), (T, H, dh)) * 0.5
        k = jax.random.normal(jax.random.PRNGKey(1), (T, H, dh)) * 0.5
        v = jax.random.normal(jax.random.PRNGKey(2), (T, H, dh))
        out = swa_attention(q, k, v, window=W, block_q=64, block_k=64)
        want = ref.swa_attention_ref(q, k, v, W)
        assert np.abs(np.asarray(out) - np.asarray(want)).max() < 2e-4

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                           (jnp.bfloat16, 3e-2)])
    def test_dtypes(self, dtype, tol):
        T, H, dh, W = 128, 2, 64, 48
        q = (jax.random.normal(jax.random.PRNGKey(0), (T, H, dh)) * 0.5
             ).astype(dtype)
        k = (jax.random.normal(jax.random.PRNGKey(1), (T, H, dh)) * 0.5
             ).astype(dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (T, H, dh)).astype(dtype)
        out = swa_attention(q, k, v, window=W)
        want = ref.swa_attention_ref(q, k, v, W)
        assert out.dtype == dtype
        assert np.abs(np.asarray(out, np.float32) -
                      np.asarray(want, np.float32)).max() < tol

    def test_window_1_is_self_only(self):
        """window=1: each position attends only to itself -> output = v."""
        T, H, dh = 64, 1, 32
        q = jax.random.normal(jax.random.PRNGKey(0), (T, H, dh))
        k = jax.random.normal(jax.random.PRNGKey(1), (T, H, dh))
        v = jax.random.normal(jax.random.PRNGKey(2), (T, H, dh))
        out = swa_attention(q, k, v, window=1, block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(v),
                                   rtol=1e-5, atol=1e-5)

    def test_full_window_matches_causal_softmax(self):
        """window >= T reduces to plain causal attention."""
        T, H, dh = 96, 2, 32
        q = jax.random.normal(jax.random.PRNGKey(0), (T, H, dh)) * 0.3
        k = jax.random.normal(jax.random.PRNGKey(1), (T, H, dh)) * 0.3
        v = jax.random.normal(jax.random.PRNGKey(2), (T, H, dh))
        out = swa_attention(q, k, v, window=T, block_q=32, block_k=32)
        # dense causal reference
        s = np.einsum("qhd,khd->hqk", np.asarray(q), np.asarray(k)
                      ) / np.sqrt(dh)
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hqk,khd->qhd", p, np.asarray(v))
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4,
                                   atol=2e-4)

    @settings(deadline=None, max_examples=15)
    @given(st.integers(16, 160), st.integers(1, 3),
           st.sampled_from([16, 32, 64]), st.integers(1, 160),
           st.integers(0, 2**16))
    def test_property_matches_oracle(self, T, H, dh, W, seed):
        q = jax.random.normal(jax.random.PRNGKey(seed), (T, H, dh)) * 0.4
        k = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, H, dh)) * 0.4
        v = jax.random.normal(jax.random.PRNGKey(seed + 2), (T, H, dh))
        out = swa_attention(q, k, v, window=W, block_q=32, block_k=32)
        want = ref.swa_attention_ref(q, k, v, W)
        assert np.abs(np.asarray(out) - np.asarray(want)).max() < 3e-4


def _greedy_inputs(C, B, seed, gamma=0.5, with_need=False):
    """Kernel-shaped greedy inputs for a TO matrix: the coverage-weight
    matrix plus per-trial (order, epick, need_row) exactly as
    ``greedy_row_assignment_batch`` builds them."""
    from repro.core.scheduling import _greedy_matrices
    C = np.asarray(C)
    n = C.shape[0]
    C_tup = tuple(tuple(int(v) for v in row) for row in C)
    W, A = _greedy_matrices(C_tup, float(gamma))
    est = jax.random.uniform(jax.random.PRNGKey(seed), (B, n),
                             minval=0.01, maxval=1.0)
    order = jnp.argsort(est, axis=-1).astype(jnp.int32)
    epick = jnp.maximum(jnp.take_along_axis(est, order, axis=-1),
                        jnp.float32(1e-30))
    need_row = None
    if with_need:
        need = (jax.random.uniform(jax.random.PRNGKey(seed + 1), (B, n))
                < 0.3).astype(jnp.float32)
        need_row = need @ jnp.asarray(A).T
    return jnp.asarray(W), order, epick, need_row


class TestGreedyAssign:
    """Pallas greedy row-assignment kernel vs the pure-jnp oracle.  The
    pick loop is integer-valued, so every comparison is bitwise."""

    @pytest.mark.parametrize("n,r,B,bt,seed", [
        (8, 3, 64, 128, 512),      # single partial block
        (8, 3, 128, 128, 1024),    # exactly one block
        (8, 3, 300, 128, 2400),    # multi-block with a ragged edge
        (4, 1, 17, 8, 68),         # tiny blocks, many grid steps
        (12, 12, 50, 32, 600),     # full load r = n
        (5, 4, 8, 32, 1),          # cyclic rows tied in exact arithmetic
    ])
    def test_matches_oracle(self, n, r, B, bt, seed):
        C = cyclic_to_matrix(n, r)
        W, order, epick, need_row = _greedy_inputs(C, B, seed=seed)
        out = greedy_assign(W, order, epick, need_row, block_trials=bt)
        want = ref.greedy_assign_ref(W, order, epick, need_row)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    @pytest.mark.parametrize("n,r,B", [(5, 4, 8), (16, 4, 64), (64, 8, 32)])
    def test_coverage_scores_match_float64(self, n, r, B):
        # kernel and oracle share coverage_scores; check the sum itself
        # against float64 cov @ W.T within float32 summation rounding
        from repro.kernels.greedy_assign import coverage_scores
        W, _, _, _ = _greedy_inputs(cyclic_to_matrix(n, r), B, seed=n)
        cov = jax.random.uniform(jax.random.PRNGKey(B), (B, n),
                                 minval=0.0, maxval=50.0)
        got = np.asarray(coverage_scores(cov, [W.T[t:t + 1]
                                               for t in range(n)]))
        c64, w64 = np.asarray(cov, np.float64), np.asarray(W, np.float64)
        want = c64 @ w64.T
        bound = (n + 1) * np.finfo(np.float32).eps * (np.abs(c64)
                                                     @ np.abs(w64).T)
        assert got.shape == (B, n)
        assert (np.abs(got - want) <= bound).all()

    def test_need_vector_reissue_priority(self):
        C = staircase_to_matrix(8, 3)
        W, order, epick, need_row = _greedy_inputs(C, 90, seed=5,
                                                   with_need=True)
        out = greedy_assign(W, order, epick, need_row, block_trials=32)
        want = ref.greedy_assign_ref(W, order, epick, need_row)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_tied_scores_break_to_lowest_row(self):
        """Identical estimates everywhere -> maximal score ties; the kernel
        must reproduce the oracle's lowest-row argmin tie-break."""
        n, B = 8, 40
        C = cyclic_to_matrix(n, 3)
        W, _, _, _ = _greedy_inputs(C, B, seed=0)
        est = jnp.full((B, n), 0.25, jnp.float32)
        order = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
        out = greedy_assign(W, order, est, block_trials=16)
        want = ref.greedy_assign_ref(W, order, est)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_ragged_loads(self):
        loads = [3, 1, 2, 3, 1, 3]
        C = cyclic_to_matrix(6, loads=loads)
        W, order, epick, need_row = _greedy_inputs(C, 70, seed=11,
                                                   with_need=True)
        out = greedy_assign(W, order, epick, need_row, block_trials=64)
        want = ref.greedy_assign_ref(W, order, epick, need_row)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    @pytest.mark.parametrize("with_need", [False, True])
    def test_batch_entry_point_impls_agree(self, with_need):
        """``greedy_row_assignment_batch(impl=...)`` is bitwise identical
        between the scan and the kernel, including leading batch dims."""
        n, r = 8, 3
        C = random_assignment_to_matrix(n, seed=3)
        est = jax.random.uniform(jax.random.PRNGKey(2), (5, 13, n),
                                 minval=0.01, maxval=1.0)
        need = ((jax.random.uniform(jax.random.PRNGKey(3), (5, 13, n)) < 0.4)
                .astype(jnp.float32) if with_need else None)
        a = greedy_row_assignment_batch(C, est, need=need, impl="scan")
        b = greedy_row_assignment_batch(C, est, need=need, impl="kernel")
        assert a.shape == est.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @settings(deadline=None, max_examples=25)
    @given(st.integers(3, 10), st.integers(1, 6), st.integers(1, 150),
           st.booleans(), st.integers(0, 2**16))
    def test_property_matches_oracle(self, n, r, B, with_need, seed):
        r = min(r, n)
        C = cyclic_to_matrix(n, r) if seed % 2 else staircase_to_matrix(n, r)
        W, order, epick, need_row = _greedy_inputs(C, B, seed=seed,
                                                   with_need=with_need)
        out = greedy_assign(W, order, epick, need_row, block_trials=32)
        want = ref.greedy_assign_ref(W, order, epick, need_row)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
