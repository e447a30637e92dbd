"""Layer primitives for every assigned architecture family.

Pure-JAX pytree modules: ``<name>_init(key, cfg, ...) -> params`` and
``<name>_apply(params, x, ...) -> y``. No flax/optax dependency.

Mixers: GQA attention (full / sliding-window / cross), MLA (DeepSeek-style
compressed KV), Mamba selective scan, RWKV6 time-mix.
FFNs: SwiGLU, GELU (whisper), RWKV channel-mix, MoE (capacity-based grouped
GEMM with expert-parallel shard_map — exact active FLOPs, no one-hot
dispatch tensor; see DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..sharding import BOTH, DATA, MODEL, current_mesh_ctx, shard
from .config import ModelConfig

Array = jax.Array
PyTree = Any


# --------------------------------------------------------------------------
# basics
# --------------------------------------------------------------------------

def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: float | None = None) -> PyTree:
    scale = (1.0 / math.sqrt(d_in)) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: PyTree, x: Array) -> Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def rms_norm_init(d: int, dtype=jnp.float32) -> PyTree:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p: PyTree, x: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layer_norm_init(d: int, dtype=jnp.float32) -> PyTree:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(p: PyTree, x: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def norm_init(cfg: ModelConfig) -> PyTree:
    return (layer_norm_init(cfg.d_model, _pdtype(cfg))
            if cfg.arch_type == "audio" else
            rms_norm_init(cfg.d_model, _pdtype(cfg)))


def norm(cfg: ModelConfig, p: PyTree, x: Array) -> Array:
    return (layer_norm(p, x, cfg.norm_eps) if "bias" in p
            else rms_norm(p, x, cfg.norm_eps))


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, jnp.float32) / dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x (..., T, H, dh) or (..., T, dh); positions (..., T)."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                       # (dh/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., T, dh/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == positions.ndim + 2:                  # head axis present
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention core — flash-style chunked online softmax (pure JAX)
# --------------------------------------------------------------------------

def attention_core(q: Array, k: Array, v: Array, *, causal: bool,
                   q_offset, window: Optional[int] = None,
                   kv_len=None, softcap: Optional[float] = None,
                   chunk_q: int = 2048, chunk_k: int = 1024) -> Array:
    """q (B, H, Tq, dh), k/v (B, H, Tk, dh_[v]) — same head count (GQA kv is
    repeated by the caller). ``q_offset`` (scalar) is the absolute position
    of q[...,0,:]; ``kv_len`` (scalar or None) masks cache positions >= len.
    Memory is bounded by (chunk_q x chunk_k) score tiles for long sequences.
    """
    B, H, Tq, dh = q.shape
    Tk = k.shape[2]
    scale = 1.0 / math.sqrt(dh)
    qpos = q_offset + jnp.arange(Tq)
    kpos = jnp.arange(Tk)

    def mask_bias(qp, kp):
        ok = jnp.ones((qp.shape[0], kp.shape[0]), bool)
        if causal:
            ok &= kp[None, :] <= qp[:, None]
        if window is not None:
            ok &= kp[None, :] > qp[:, None] - window
        if kv_len is not None:
            ok &= (kp < kv_len)[None, :]
        return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)

    if Tq * Tk <= 4096 * 4096 and Tq <= 4096:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = s + mask_bias(qpos, kpos)[None, None]
        p = jax.nn.softmax(s, axis=-1)
        # rows with all -inf (fully masked) produce nan -> zero them
        p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)

    # ---- chunked path ----
    nk = -(-Tk // chunk_k)
    pad_k = nk * chunk_k - Tk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    eff_len = jnp.minimum(jnp.asarray(Tk), kv_len) if kv_len is not None \
        else jnp.asarray(Tk)

    def q_block(qc, qp):
        # qc (B, H, cq, dh); online softmax over k chunks
        cq = qc.shape[2]
        m0 = jnp.full((B, H, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, v.shape[-1]), jnp.float32)

        def body(carry, i):
            m, l, acc = carry
            ks = lax.dynamic_slice_in_dim(k, i * chunk_k, chunk_k, 2)
            vs = lax.dynamic_slice_in_dim(v, i * chunk_k, chunk_k, 2)
            kp = i * chunk_k + jnp.arange(chunk_k)
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, ks,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            bias = jnp.where(kp[None, :] < eff_len, 0.0, -jnp.inf)
            ok = jnp.ones((cq, chunk_k), bool)
            if causal:
                ok &= kp[None, :] <= qp[:, None]
            if window is not None:
                ok &= kp[None, :] > qp[:, None] - window
            s = s + (jnp.where(ok, 0.0, -jnp.inf) + bias)[None, None]
            m_new = jnp.maximum(m, s.max(-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pexp = jnp.exp(s - m_safe[..., None])
            pexp = jnp.where(jnp.isfinite(s), pexp, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + pexp.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", pexp.astype(vs.dtype), vs
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)

    nq = -(-Tq // chunk_q)
    pad_q = nq * chunk_q - Tq
    qp_all = qpos
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        qp_all = jnp.pad(qpos, (0, pad_q))
    qs = q.reshape(B, H, nq, chunk_q, dh).transpose(2, 0, 1, 3, 4)
    qps = qp_all.reshape(nq, chunk_q)
    out = lax.map(lambda t: q_block(t[0], t[1]), (qs, qps))
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, H, nq * chunk_q, -1)
    return out[:, :, :Tq]


def _shard_attn_act(cfg: ModelConfig, x: Array, note: str) -> Array:
    """(B, T, H, dh) activation sharding: heads on the model axis when
    divisible; with cfg.attn_batch_shard_fallback, batch over
    (data x model) instead of replicating (§Perf variant for archs whose
    head count is smaller than the model axis, e.g. gemma3's 8 heads)."""
    ctx = current_mesh_ctx()
    if (ctx is not None and cfg.attn_batch_shard_fallback
            and x.shape[2] % ctx.model_size != 0
            and x.shape[0] % (ctx.data_size * ctx.model_size) == 0):
        return shard(x, BOTH, None, None, None, note=note)
    return shard(x, DATA, None, MODEL, None, note=note)


def grouped_attention(q: Array, kf: Array, vf: Array, *, kv_len, scale,
                      q_offset) -> Array:
    """Decode attention without repeat_kv: q (B, H, T, dh), kf/vf
    (B, K, S, dh) stay unexpanded; scores grouped by KV head (§Perf)."""
    B, H, T, dh = q.shape
    K = kf.shape[1]
    G = H // K
    qg = q.reshape(B, K, G, T, dh)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, kf,
                   preferred_element_type=jnp.float32) * scale
    S = kf.shape[2]
    kpos = jnp.arange(S)
    qpos = q_offset + jnp.arange(T)
    ok = (kpos[None, :] < kv_len) & (kpos[None, :] <= qpos[:, None])
    s = jnp.where(ok[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bksd->bkgtd", p.astype(vf.dtype), vf)
    return out.reshape(B, H, T, dh)


def seq_sharded_decode_attention(cfg: ModelConfig, q: Array, kx: Array,
                                 vx: Array, cache: dict) -> Tuple[Array,
                                                                  dict]:
    """Single-token decode against a KV cache whose SEQUENCE dim is sharded
    over the model axis (§Perf 'ringdecode'): each shard updates its slice
    (if it owns the write position), computes a local flash partial, and
    the global softmax is assembled with one pmax + two psums of
    (B, H, dh)-sized tensors — instead of SPMD all-gathering the cache.

    q (B, H, 1, dh); kx/vx (B, K, 1, dh); cache {k, v (B, K, S, dh), pos}.
    Returns (out (B, H, 1, dh), new_cache).
    """
    ctx = current_mesh_ctx()
    B, H, _, dh = q.shape
    K = kx.shape[1]
    G = H // K
    pos = cache["pos"]
    maxes = ctx.model_axis
    dspec = ctx.resolve(DATA) if B % ctx.data_size == 0 else None
    scale = 1.0 / math.sqrt(dh)

    def block(q_l, kx_l, vx_l, ck, cv, pos_):
        Bl = q_l.shape[0]                           # local batch
        Sl = ck.shape[2]                            # local cache slice
        o = lax.axis_index(maxes) * Sl
        idx = pos_ - o
        in_range = (idx >= 0) & (idx < Sl)
        safe = jnp.clip(idx, 0, Sl - 1)
        ck = ck.at[:, :, safe].set(
            jnp.where(in_range, kx_l[:, :, 0], ck[:, :, safe]))
        cv = cv.at[:, :, safe].set(
            jnp.where(in_range, vx_l[:, :, 0], cv[:, :, safe]))
        kpos = o + jnp.arange(Sl)
        valid = kpos <= pos_                        # causal + kv_len
        qg = q_l.reshape(Bl, K, G, dh)
        s = jnp.einsum("bkgd,bksd->bkgs", qg, ck,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None, None, None], s, -jnp.inf)
        m_loc = s.max(-1)                           # (Bl, K, G)
        m_glob = lax.pmax(m_loc, maxes)
        p = jnp.exp(s - m_glob[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        l = lax.psum(p.sum(-1), maxes)              # (Bl, K, G)
        o_part = jnp.einsum("bkgs,bksd->bkgd", p.astype(cv.dtype), cv)
        o_full = lax.psum(o_part.astype(jnp.float32), maxes)
        out = (o_full / jnp.maximum(l, 1e-30)[..., None]).astype(q_l.dtype)
        return out.reshape(Bl, H, 1, dh), ck, cv

    out, kf, vf = jax.shard_map(
        block, mesh=ctx.mesh,
        in_specs=(P(dspec, None, None, None), P(dspec, None, None, None),
                  P(dspec, None, None, None), P(dspec, None, maxes, None),
                  P(dspec, None, maxes, None), P()),
        out_specs=(P(dspec, None, None, None), P(dspec, None, maxes, None),
                   P(dspec, None, maxes, None)),
    )(q, kx, vx, cache["k"], cache["v"], pos)
    return out, {"k": kf, "v": vf, "pos": pos + 1}


def repeat_kv(x: Array, groups: int) -> Array:
    """(B, K, T, dh) -> (B, K*groups, T, dh)."""
    if groups == 1:
        return x
    B, K, T, dh = x.shape
    return jnp.broadcast_to(x[:, :, None], (B, K, groups, T, dh)
                            ).reshape(B, K * groups, T, dh)


# --------------------------------------------------------------------------
# GQA attention (full / sliding-window / cross) with optional KV cache
# --------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig) -> PyTree:
    dh, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = _pdtype(cfg)
    return {
        "wq": dense_init(ks[0], cfg.d_model, H * dh, bias=cfg.qkv_bias, dtype=dt),
        "wk": dense_init(ks[1], cfg.d_model, K * dh, bias=cfg.qkv_bias, dtype=dt),
        "wv": dense_init(ks[2], cfg.d_model, K * dh, bias=cfg.qkv_bias, dtype=dt),
        "wo": dense_init(ks[3], H * dh, cfg.d_model, dtype=dt,
                         scale=1.0 / math.sqrt(H * dh)),
    }


def gqa_apply(p: PyTree, cfg: ModelConfig, x: Array, *, window=None,
              positions=None, cache=None, xattn_kv=None,
              use_rope=True, causal=True) -> Tuple[Array, Optional[PyTree]]:
    """x (B, T, d). ``cache`` = {"k","v","pos"} for decode; ``xattn_kv`` =
    (k, v) (B, H, Tk, dh) precomputed cross-attention keys/values."""
    B, T, d = x.shape
    dh, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = dense(p["wq"], x).reshape(B, T, H, dh)
    q = _shard_attn_act(cfg, q, "attn.q")
    if positions is None:
        positions = jnp.arange(T)[None, :]

    if xattn_kv is not None:
        kf, vf = xattn_kv
        q = q.transpose(0, 2, 1, 3)
        out = attention_core(q, kf, vf, causal=False, q_offset=0)
        new_cache = cache
    else:
        kx = dense(p["wk"], x).reshape(B, T, K, dh)
        vx = dense(p["wv"], x).reshape(B, T, K, dh)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            kx = apply_rope(kx, positions, cfg.rope_theta)
        q = q.transpose(0, 2, 1, 3)            # (B, H, T, dh)
        kx = kx.transpose(0, 2, 1, 3)
        vx = vx.transpose(0, 2, 1, 3)
        if cache is None:
            new_cache = None
            out = attention_core(q, repeat_kv(kx, H // K),
                                 repeat_kv(vx, H // K),
                                 causal=causal, q_offset=0, window=window,
                                 softcap=cfg.attn_logit_softcap)
        else:
            pos = cache["pos"]                 # scalar int32: tokens so far
            S = cache["k"].shape[2]
            if window is not None and S < cfg.max_seq_len:
                # ring buffer of size S == window; supports chunked prefill.
                # Attend over [pre-write ring | current chunk], then write.
                slot = jnp.arange(S)
                qpos = pos + jnp.arange(T)
                # latest absolute position per ring slot BEFORE this chunk
                abs_old = (pos - 1) - ((pos - 1 - slot) % S)
                k_all = jnp.concatenate([cache["k"], kx], axis=2)
                v_all = jnp.concatenate([cache["v"], vx], axis=2)
                kpos = jnp.concatenate([abs_old, pos + jnp.arange(T)])
                valid = (kpos[None, :] >= 0) & \
                        (kpos[None, :] <= qpos[:, None]) & \
                        (kpos[None, :] > qpos[:, None] - window)
                s = jnp.einsum("bhqd,bhkd->bhqk", q,
                               repeat_kv(k_all, H // K),
                               preferred_element_type=jnp.float32
                               ) / math.sqrt(dh)
                s = jnp.where(valid[None, None], s, -jnp.inf)
                w_ = jax.nn.softmax(s, axis=-1)
                w_ = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), w_, 0.)
                out = jnp.einsum("bhqk,bhkd->bhqd", w_.astype(x.dtype),
                                 repeat_kv(v_all, H // K))
                t0 = max(0, T - S)          # only the last S tokens persist
                slots_w = (pos + t0 + jnp.arange(T - t0)) % S
                kf = cache["k"].at[:, :, slots_w].set(kx[:, :, t0:])
                vf = cache["v"].at[:, :, slots_w].set(vx[:, :, t0:])
                new_cache = {"k": kf, "v": vf, "pos": pos + T}
                o = out.transpose(0, 2, 1, 3).reshape(B, T, H * dh)
                o = shard(o, DATA, None, None, note="attn.o")
                return dense(p["wo"], o), new_cache
            ctx_ = current_mesh_ctx()
            if (cfg.seq_shard_decode and T == 1 and window is None
                    and cfg.attn_logit_softcap is None and ctx_ is not None
                    and ctx_.model_size > 1
                    and S % ctx_.model_size == 0):
                out, new_cache = seq_sharded_decode_attention(
                    cfg, q, kx, vx, cache)
                o = out.transpose(0, 2, 1, 3).reshape(B, T, H * dh)
                o = shard(o, DATA, None, None, note="attn.o")
                return dense(p["wo"], o), new_cache
            kf = lax.dynamic_update_slice_in_dim(cache["k"], kx, pos, 2)
            vf = lax.dynamic_update_slice_in_dim(cache["v"], vx, pos, 2)
            new_cache = {"k": kf, "v": vf, "pos": pos + T}
            if cfg.grouped_gqa and window is None \
                    and cfg.attn_logit_softcap is None:
                out = grouped_attention(q, kf, vf, kv_len=pos + T,
                                        scale=1.0 / math.sqrt(dh),
                                        q_offset=pos)
            else:
                out = attention_core(q, repeat_kv(kf, H // K),
                                     repeat_kv(vf, H // K), causal=True,
                                     q_offset=pos, window=window,
                                     kv_len=pos + T,
                                     softcap=cfg.attn_logit_softcap)
    o = out.transpose(0, 2, 1, 3).reshape(B, T, H * dh)
    o = shard(o, DATA, None, None, note="attn.o")
    return dense(p["wo"], o), new_cache


def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int, *,
                   window: Optional[int] = None) -> PyTree:
    S = min(window, max_len) if window else max_len
    dt = _dtype(cfg)
    return {"k": jnp.zeros((batch, cfg.n_kv_heads, S, cfg.head_dim), dt),
            "v": jnp.zeros((batch, cfg.n_kv_heads, S, cfg.head_dim), dt),
            "pos": jnp.zeros((), jnp.int32)}


# --------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention (compressed KV cache)
# --------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig) -> PyTree:
    dt = _pdtype(cfg)
    H = cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 7)
    p = {
        "w_dkv": dense_init(ks[0], cfg.d_model, cfg.kv_lora_rank, dtype=dt),
        "kv_norm": rms_norm_init(cfg.kv_lora_rank, dt),
        "w_uk": dense_init(ks[1], cfg.kv_lora_rank,
                           H * (cfg.qk_nope_dim + cfg.v_head_dim), dtype=dt),
        "w_kr": dense_init(ks[2], cfg.d_model, cfg.qk_rope_dim, dtype=dt),
        "wo": dense_init(ks[3], H * cfg.v_head_dim, cfg.d_model, dtype=dt),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = dense_init(ks[4], cfg.d_model, cfg.q_lora_rank, dtype=dt)
        p["q_norm"] = rms_norm_init(cfg.q_lora_rank, dt)
        p["w_uq"] = dense_init(ks[5], cfg.q_lora_rank, H * qk, dtype=dt)
    else:
        p["w_uq"] = dense_init(ks[5], cfg.d_model, H * qk, dtype=dt)
    return p


def mla_apply(p: PyTree, cfg: ModelConfig, x: Array, *, positions=None,
              cache=None) -> Tuple[Array, Optional[PyTree]]:
    B, T, d = x.shape
    H = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(T)[None, :]
    # queries
    if "w_dq" in p:
        ql = rms_norm(p["q_norm"], dense(p["w_dq"], x), cfg.norm_eps)
    else:
        ql = x
    q = dense(p["w_uq"], ql).reshape(B, T, H, nd + rd)
    q = shard(q, DATA, None, MODEL, None, note="mla.q")
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    # compressed kv
    c_kv = rms_norm(p["kv_norm"], dense(p["w_dkv"], x), cfg.norm_eps)  # (B,T,R)
    k_rope = apply_rope(dense(p["w_kr"], x), positions, cfg.rope_theta)  # (B,T,rd)
    if cache is not None:
        pos = cache["pos"]
        c_kv = lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv, pos, 1)
        k_rope = lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope,
                                                 pos, 1)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope, "pos": pos + T}
        kv_len = pos + T
        q_offset = pos
    else:
        new_cache = None
        kv_len = None
        q_offset = 0

    if cfg.mla_absorb and cache is not None:
        # --- absorbed path (§Perf): attention entirely in the compressed
        # latent space. q_nope is absorbed through W_uk's key half
        # (q̃ = q_nope · W_uk_k), scores = q̃ · c_kv^T + q_rope · k_rope^T,
        # and the context is projected out through W_uk's value half.
        # Avoids materializing (B, S, H, nd+vd) decompressed K/V.
        R = cfg.kv_lora_rank
        wk = p["w_uk"]["w"].astype(x.dtype).reshape(R, H, nd + vd)
        w_uk_k, w_uk_v = wk[..., :nd], wk[..., nd:]
        q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk_k)
        s = jnp.einsum("bthr,bsr->bhts", q_lat, c_kv,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope,
                           preferred_element_type=jnp.float32)
        s = s / math.sqrt(nd + rd)
        S_ = c_kv.shape[1]
        kpos = jnp.arange(S_)
        qpos = q_offset + jnp.arange(T)
        ok = (kpos[None, :] < kv_len) & (kpos[None, :] <= qpos[:, None])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        ctx_lat = jnp.einsum("bhts,bsr->bthr", pr.astype(x.dtype), c_kv)
        out_h = jnp.einsum("bthr,rhv->bthv", ctx_lat, w_uk_v)
        o = out_h.reshape(B, T, H * vd)
        o = shard(o, DATA, None, None, note="mla.o")
        return dense(p["wo"], o), new_cache

    # decompress (naive path; absorbed path above is the §Perf variant)
    kv = dense(p["w_uk"], c_kv).reshape(B, c_kv.shape[1], H, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], rd))], axis=-1)
    qh = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = attention_core(qh, kh, vh, causal=True, q_offset=q_offset,
                         kv_len=kv_len)
    o = out.transpose(0, 2, 1, 3).reshape(B, T, H * vd)
    o = shard(o, DATA, None, None, note="mla.o")
    return dense(p["wo"], o), new_cache


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    dt = _dtype(cfg)
    return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dt),
            "pos": jnp.zeros((), jnp.int32)}


# --------------------------------------------------------------------------
# FFNs
# --------------------------------------------------------------------------

def swiglu_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> PyTree:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = _pdtype(cfg)
    return {"w_gate": dense_init(ks[0], cfg.d_model, d_ff, dtype=dt),
            "w_up": dense_init(ks[1], cfg.d_model, d_ff, dtype=dt),
            "w_down": dense_init(ks[2], d_ff, cfg.d_model, dtype=dt,
                                 scale=1.0 / math.sqrt(d_ff))}


def swiglu_apply(p: PyTree, x: Array) -> Array:
    h = jax.nn.silu(dense(p["w_gate"], x)) * dense(p["w_up"], x)
    h = shard(h, DATA, None, MODEL, note="ffn.h")
    return dense(p["w_down"], h)


def gelu_mlp_init(key, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(key, 2)
    dt = _pdtype(cfg)
    return {"w_up": dense_init(ks[0], cfg.d_model, cfg.d_ff, bias=True, dtype=dt),
            "w_down": dense_init(ks[1], cfg.d_ff, cfg.d_model, bias=True,
                                 dtype=dt, scale=1.0 / math.sqrt(cfg.d_ff))}


def gelu_mlp_apply(p: PyTree, x: Array) -> Array:
    h = jax.nn.gelu(dense(p["w_up"], x))
    h = shard(h, DATA, None, MODEL, note="ffn.h")
    return dense(p["w_down"], h)


# RWKV channel-mix (relu^2 MLP with token shift + receptance gate)
def cmix_init(key, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(key, 3)
    dt = _pdtype(cfg)
    return {"mu_k": jnp.full((cfg.d_model,), 0.5, dt),
            "mu_r": jnp.full((cfg.d_model,), 0.5, dt),
            "w_k": dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype=dt),
            "w_v": dense_init(ks[1], cfg.d_ff, cfg.d_model, dtype=dt,
                              scale=1.0 / math.sqrt(cfg.d_ff)),
            "w_r": dense_init(ks[2], cfg.d_model, cfg.d_model, dtype=dt)}


def _token_shift(x: Array, prev: Optional[Array]) -> Array:
    """x (B, T, d) -> x shifted right by one along T; position 0 gets
    ``prev`` (B, d) or zeros."""
    first = jnp.zeros_like(x[:, :1]) if prev is None else prev[:, None, :]
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def cmix_apply(p: PyTree, x: Array, prev: Optional[Array] = None
               ) -> Tuple[Array, Array]:
    xs = _token_shift(x, prev)
    xk = x + (xs - x) * p["mu_k"].astype(x.dtype)
    xr = x + (xs - x) * p["mu_r"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(dense(p["w_k"], xk)))
    k = shard(k, DATA, None, MODEL, note="cmix.h")
    r = jax.nn.sigmoid(dense(p["w_r"], xr))
    return r * dense(p["w_v"], k), x[:, -1]


# --------------------------------------------------------------------------
# MoE — capacity-based grouped GEMM, expert-parallel via shard_map
# --------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> PyTree:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    dt = _pdtype(cfg)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * s_in
                   ).astype(jnp.float32),  # router kept f32 for stable top-k
        "w_gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * s_in
                   ).astype(dt),
        "w_up": (jax.random.normal(ks[2], (E, d, f), jnp.float32) * s_in
                 ).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32) * s_out
                   ).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(
            ks[4], cfg, d_ff=(cfg.d_ff_expert or cfg.d_ff) * cfg.n_shared_experts)
    return p


def _moe_local(x2d: Array, router_w: Array, w_gate: Array, w_up: Array,
               w_down: Array, *, cfg: ModelConfig, e_start,
               n_local: int) -> Tuple[Array, Array]:
    """Grouped-GEMM MoE over ``n_local`` experts starting at ``e_start``.
    x2d (T, d). Returns (out (T, d) — contributions of local experts only,
    aux load-balance loss (scalar, local estimate))."""
    T, d = x2d.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    C = max(1, math.ceil(T * K / E * cfg.capacity_factor))
    logits = x2d.astype(jnp.float32) @ router_w              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(probs, K)                       # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e f_e * P_e
    f_e = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (T * K)
    P_e = probs.mean(0)
    aux = E * jnp.sum(f_e * P_e)

    flat_i = top_i.reshape(-1)                               # (T*K,)
    flat_w = top_w.reshape(-1).astype(x2d.dtype)
    tok = jnp.arange(T * K) // K
    local = flat_i - e_start
    valid = (local >= 0) & (local < n_local)
    key_ = jnp.where(valid, local, n_local)
    order = jnp.argsort(key_, stable=True)
    skey = key_[order]
    counts = jnp.zeros((n_local + 1,), jnp.int32).at[skey].add(1)
    starts = jnp.cumsum(counts) - counts                     # exclusive
    pos = jnp.arange(T * K) - starts[skey]
    ok = (skey < n_local) & (pos < C)
    slot = jnp.where(ok, skey * C + pos, n_local * C)        # overflow -> trash
    buf = jnp.zeros((n_local * C + 1, d), x2d.dtype)
    buf = buf.at[slot].set(jnp.where(ok[:, None], x2d[tok[order]], 0))
    eb = buf[:n_local * C].reshape(n_local, C, d)
    h = jnp.einsum("ecd,edf->ecf", eb, w_gate.astype(eb.dtype))
    u = jnp.einsum("ecd,edf->ecf", eb, w_up.astype(eb.dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                   w_down.astype(eb.dtype))
    yf = jnp.concatenate([y.reshape(n_local * C, d),
                          jnp.zeros((1, d), y.dtype)], 0)
    contrib = yf[slot] * (flat_w[order] * ok.astype(x2d.dtype))[:, None]
    out = jnp.zeros((T, d), x2d.dtype).at[tok[order]].add(contrib)
    return out, aux


def moe_apply(p: PyTree, cfg: ModelConfig, x: Array) -> Tuple[Array, Array]:
    """x (B, T, d) -> (out, aux_loss). Expert-parallel over the model axis
    when available and divisible; shared experts run dense (tensor-parallel).
    """
    B, T, d = x.shape
    x2 = x.reshape(B * T, d)
    ctx = current_mesh_ctx()
    E = cfg.n_experts
    msize = ctx.model_size if ctx is not None else 1
    if ctx is not None and msize > 1 and E % msize == 0:
        n_local = E // msize
        maxes = ctx.model_axis
        data_axes = tuple(ctx.data_axes)
        all_axes = data_axes + (maxes,)
        # tokens shard over the data axes when divisible; batch-1 decode
        # keeps tokens replicated (expert weights stay model-sharded).
        tokens_sharded = ctx.data_size > 1 and (B * T) % ctx.data_size == 0
        dspec = ctx.resolve(DATA) if tokens_sharded else None

        def block(xl, rw, wg, wu, wd):
            e_start = lax.axis_index(maxes) * n_local
            out, aux = _moe_local(xl, rw, wg, wu, wd, cfg=cfg,
                                  e_start=e_start, n_local=n_local)
            out = lax.psum(out, maxes)
            # aux: sum disjoint local f_e*P_e terms over experts (model
            # axis), mean over data shards; cast the axes the tracker
            # sees as invarying to varying, then psum over everything so
            # the scalar is replicated (out_specs P()).
            aux = lax.pcast(aux, (maxes,) if tokens_sharded else all_axes,
                            to="varying")
            aux = lax.psum(aux, all_axes) / ctx.data_size
            return out, aux

        out, aux = jax.shard_map(
            block, mesh=ctx.mesh,
            in_specs=(P(dspec, None), P(None, None), P(maxes, None, None),
                      P(maxes, None, None), P(maxes, None, None)),
            out_specs=(P(dspec, None), P()),
        )(x2, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        out, aux = _moe_local(x2, p["router"], p["w_gate"], p["w_up"],
                              p["w_down"], cfg=cfg, e_start=0, n_local=E)
    out = out.reshape(B, T, d)
    if "shared" in p:
        out = out + swiglu_apply(p["shared"], x)
    return out, aux


# --------------------------------------------------------------------------
# Mamba (selective scan, Jamba-style) — sequential lax.scan over time
# --------------------------------------------------------------------------

def mamba_init(key, cfg: ModelConfig) -> PyTree:
    d, di, N = cfg.d_model, cfg.d_inner, cfg.d_state
    dt_rank = max(1, math.ceil(d / 16))
    ks = jax.random.split(key, 6)
    dt = _pdtype(cfg)
    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))
    return {
        "in_proj": dense_init(ks[0], d, 2 * di, dtype=dt),
        "conv_w": (jax.random.normal(ks[1], (cfg.d_conv, di), jnp.float32)
                   / math.sqrt(cfg.d_conv)).astype(dt),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": dense_init(ks[2], di, dt_rank + 2 * N, dtype=dt),
        "dt_proj": dense_init(ks[3], dt_rank, di, bias=True, dtype=dt),
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[4], di, d, dtype=dt,
                               scale=1.0 / math.sqrt(di)),
    }


def _mamba_conv(x: Array, w: Array, b: Array, prev: Optional[Array]
                ) -> Tuple[Array, Array]:
    """Causal depthwise conv over (B, T, di) with kernel (d_conv, di).
    ``prev`` (B, d_conv-1, di) carries state for decode."""
    dconv = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], dconv - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
              for i in range(dconv))
    new_prev = xp[:, -(dconv - 1):] if dconv > 1 else prev
    return out + b.astype(x.dtype), new_prev


def mamba_apply(p: PyTree, cfg: ModelConfig, x: Array, state=None
                ) -> Tuple[Array, Optional[PyTree]]:
    """x (B, T, d); state {"h": (B, di, N), "conv": (B, d_conv-1, di)}."""
    B, T, d = x.shape
    di, N = cfg.d_inner, cfg.d_state
    dt_rank = p["dt_proj"]["w"].shape[0]
    xz = dense(p["in_proj"], x)
    x1, z = jnp.split(xz, 2, axis=-1)
    x1 = shard(x1, DATA, None, MODEL, note="mamba.x")
    conv_prev = None if state is None else state["conv"]
    x1, conv_new = _mamba_conv(x1, p["conv_w"], p["conv_b"], conv_prev)
    x1 = jax.nn.silu(x1)
    dbc = dense(p["x_proj"], x1)
    dt_, Bm, Cm = jnp.split(dbc, [dt_rank, dt_rank + N], axis=-1)
    delta = jax.nn.softplus(dense(p["dt_proj"], dt_))       # (B, T, di)
    A = -jnp.exp(p["A_log"])                                 # (di, N) f32
    a = jnp.exp(delta.astype(jnp.float32)[..., None] * A)    # (B, T, di, N)
    bx = (delta * x1).astype(jnp.float32)[..., None] * \
        Bm.astype(jnp.float32)[:, :, None, :]                # (B, T, di, N)

    h0 = (jnp.zeros((B, di, N), jnp.float32) if state is None
          else state["h"])

    def step(h, inp):
        a_t, bx_t, c_t = inp
        h = a_t * h + bx_t                                   # (B, di, N)
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    aT = a.transpose(1, 0, 2, 3)
    bxT = bx.transpose(1, 0, 2, 3)
    cT = Cm.astype(jnp.float32).transpose(1, 0, 2)
    hT, yT = lax.scan(step, h0, (aT, bxT, cT))
    y = yT.transpose(1, 0, 2).astype(x.dtype)                # (B, T, di)
    y = y + x1 * p["D"].astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = dense(p["out_proj"], y)
    new_state = None if state is None else {"h": hT, "conv": conv_new}
    return out, new_state


def mamba_state_init(cfg: ModelConfig, batch: int) -> PyTree:
    return {"h": jnp.zeros((batch, cfg.d_inner, cfg.d_state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner),
                              _dtype(cfg))}


# --------------------------------------------------------------------------
# RWKV6 time-mix (Finch) — data-dependent decay, lax.scan over time
# --------------------------------------------------------------------------

def rwkv6_init(key, cfg: ModelConfig) -> PyTree:
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    ks = jax.random.split(key, 8)
    dt = _pdtype(cfg)
    lora = max(32, d // 32)
    return {
        "mu": jnp.full((5, d), 0.5, dt),                   # r,k,v,w,g shifts
        "w_r": dense_init(ks[0], d, d, dtype=dt),
        "w_k": dense_init(ks[1], d, d, dtype=dt),
        "w_v": dense_init(ks[2], d, d, dtype=dt),
        "w_g": dense_init(ks[3], d, d, dtype=dt),
        "w0": jnp.full((d,), -6.0, jnp.float32),           # base decay (slow)
        "w_lora_a": dense_init(ks[4], d, lora, dtype=dt),
        "w_lora_b": dense_init(ks[5], lora, d, dtype=dt, scale=0.01),
        "u": (jax.random.normal(ks[6], (H, dh), jnp.float32) * 0.1),
        "ln_out": {"scale": jnp.ones((H, dh), jnp.float32),
                   "bias": jnp.zeros((H, dh), jnp.float32)},
        "w_o": dense_init(ks[7], d, d, dtype=dt, scale=1.0 / math.sqrt(d)),
    }


def rwkv6_apply(p: PyTree, cfg: ModelConfig, x: Array, state=None
                ) -> Tuple[Array, Optional[PyTree]]:
    """x (B, T, d); state {"S": (B, H, dh, dh) f32, "x_prev": (B, d)}."""
    B, T, d = x.shape
    H = cfg.n_heads
    dh = d // H
    prev = None if state is None else state["x_prev"]
    xs = _token_shift(x, prev)
    mu = p["mu"].astype(x.dtype)
    xr, xk, xv, xw, xg = (x + (xs - x) * mu[i] for i in range(5))
    r = dense(p["w_r"], xr).reshape(B, T, H, dh)
    k = dense(p["w_k"], xk).reshape(B, T, H, dh)
    v = dense(p["w_v"], xv).reshape(B, T, H, dh)
    g = jax.nn.silu(dense(p["w_g"], xg))
    # data-dependent decay (Finch): w = exp(-exp(w0 + lora(xw)))
    wl = dense(p["w_lora_b"], jnp.tanh(dense(p["w_lora_a"], xw)))
    w = jnp.exp(-jnp.exp(p["w0"] + wl.astype(jnp.float32)))  # (B,T,d) in (0,1)
    w = w.reshape(B, T, H, dh)
    u = p["u"]                                               # (H, dh)

    S0 = (jnp.zeros((B, H, dh, dh), jnp.float32) if state is None
          else state["S"])

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                             # (B, H, dh)
        kv = k_t[..., :, None] * v_t[..., None, :]           # (B,H,dh,dh)
        y = jnp.einsum("bhj,bhji->bhi", r_t, S + u[..., None] * kv)
        S = w_t[..., None] * S + kv
        return S, y

    rT = r.transpose(1, 0, 2, 3).astype(jnp.float32)
    kT = k.transpose(1, 0, 2, 3).astype(jnp.float32)
    vT = v.transpose(1, 0, 2, 3).astype(jnp.float32)
    wT = w.transpose(1, 0, 2, 3)
    ST, yT = lax.scan(step, S0, (rT, kT, vT, wT))
    y = yT.transpose(1, 0, 2, 3)                             # (B, T, H, dh)
    # per-head groupnorm
    mu_ = y.mean(-1, keepdims=True)
    var = y.var(-1, keepdims=True)
    y = (y - mu_) * lax.rsqrt(var + 1e-5)
    y = y * p["ln_out"]["scale"] + p["ln_out"]["bias"]
    y = y.reshape(B, T, d).astype(x.dtype) * g
    out = dense(p["w_o"], y)
    new_state = None if state is None else {"S": ST, "x_prev": x[:, -1]}
    return out, new_state


def rwkv6_state_init(cfg: ModelConfig, batch: int) -> PyTree:
    dh = cfg.d_model // cfg.n_heads
    return {"S": jnp.zeros((batch, cfg.n_heads, dh, dh), jnp.float32),
            "x_prev": jnp.zeros((batch, cfg.d_model), _dtype(cfg))}
