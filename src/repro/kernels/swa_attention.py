"""Pallas TPU flash-style causal sliding-window attention.

Enables ``long_500k`` decode/prefill on dense architectures (DESIGN.md §5):
compute per query tile touches only the KV tiles inside the window, so cost
is O(T * W) instead of O(T^2).

Grid (H, nq, nkv_vis): for query tile i, only ``nkv_vis = W/bk + 1`` KV
tiles can be visible; the KV block index map clamps ``i - nkv_vis + 1 + j``
into range and the in-kernel mask removes any out-of-window/acausal pair.
Online softmax state (m, l, acc) lives in VMEM scratch, f32; the epilogue
normalizes on the last KV step. Block sizes are MXU-aligned (128).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gram_matvec import resolve_interpret

NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                window: int, bq: int, bk: int, nkv_vis: int, seq: int):
    i = pl.program_id(1)       # query tile
    j = pl.program_id(2)       # visible-KV step

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                     # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    # absolute positions: the KV tile index was clamped in the index map,
    # so recompute it here the same way to build the mask. A clamped
    # (raw < 0) visit duplicates tile 0 — mask it out entirely, otherwise
    # its softmax mass would be double-counted.
    raw = i - nkv_vis + 1 + j
    kt = jnp.maximum(raw, 0)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kt * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = (kpos <= qpos) & (kpos > qpos - window) & (kpos < seq) & \
        (qpos < seq) & (raw >= 0)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nkv_vis - 1)
    def _():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def swa_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         window: int, block_q: int = 128,
                         block_k: int = 128,
                         interpret: bool | None = None) -> jax.Array:
    """q/k/v (T, H, dh) -> (T, H, dh); causal, window-limited attention.
    ``interpret`` defaults to backend-aware: compiled on TPU, interpreted
    elsewhere (the VMEM scratch shapes are TPU-specific)."""
    interpret = resolve_interpret(interpret, tpu_only=True)
    T, H, dh = q.shape
    bq, bk = min(block_q, T), min(block_k, T)
    pad = (-T) % max(bq, bk)
    bq = bk = min(bq, bk)
    pad = (-T) % bq
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    Tp = T + pad
    nq = Tp // bq
    nkv_vis = min(nq, window // bk + 2)   # tiles a query tile can see

    qh = q.transpose(1, 0, 2)             # (H, T, dh)
    kh = k.transpose(1, 0, 2)
    vh = v.transpose(1, 0, 2)

    def kv_index(h, i, j):
        return (h, jnp.maximum(i - nkv_vis + 1 + j, 0), 0)

    out = pl.pallas_call(
        functools.partial(_swa_kernel, window=window, bq=bq, bk=bk,
                          nkv_vis=nkv_vis, seq=T),
        grid=(H, nq, nkv_vis),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Tp, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
        name="swa_attention",
    )(qh, kh, vh)
    return out.transpose(1, 0, 2)[:T]
