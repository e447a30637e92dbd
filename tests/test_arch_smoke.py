"""Per-architecture smoke tests (deliverable (f)): a REDUCED variant of each
assigned architecture family (<= 2 layers, d_model <= 512, <= 4 experts)
runs one forward + one straggler train step on CPU; output shapes asserted,
no NaNs. Full configs are exercised only via the dry-run."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, SHAPES, input_specs, \
    shape_supported, long_variant
from repro.core import RoundConfig, scenario1
from repro.data import TaskPartition, lm_task_batches
from repro.models import (init_params, forward, init_cache, num_params,
                          layer_specs)
from repro.optim import adamw
from repro.train import init_train_state, make_straggler_train_step, \
    make_train_step


def _smoke_cfg(arch):
    cfg = get_config(arch).smoke()
    if cfg.arch_type == "hybrid":
        # make sure the 2-layer smoke variant still has one attn layer
        cfg = dataclasses.replace(cfg, ssm_period=2, ssm_attn_offset=1)
    return cfg


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_no_nan(self, arch):
        cfg = _smoke_cfg(arch)
        assert cfg.n_layers <= 2 and cfg.d_model <= 512
        assert cfg.n_experts <= 4
        key = jax.random.PRNGKey(0)
        params = init_params(key, cfg)
        B, T = 2, 16
        toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
        kwargs = {}
        if cfg.frontend_seq:
            kwargs["embeds"] = jax.random.normal(
                key, (B, cfg.frontend_seq, cfg.frontend_dim))
        if cfg.encoder_layers:
            kwargs["enc_frames"] = jax.random.normal(
                key, (B, cfg.encoder_seq, cfg.frontend_dim))
        logits, aux, _ = forward(params, cfg, toks, **kwargs)
        exp_T = T + (cfg.frontend_seq or 0)
        assert logits.shape == (B, exp_T, cfg.padded_vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all(), \
            f"{arch}: NaN/inf in logits"
        assert np.isfinite(float(aux))

    def test_one_train_step(self, arch):
        cfg = _smoke_cfg(arch)
        opt = adamw(1e-3)
        key = jax.random.PRNGKey(1)
        state = init_train_state(key, cfg, opt)
        B, T = 4, 16
        toks = jax.random.randint(key, (B, T + 1), 0, cfg.vocab_size)
        extras = {}
        if cfg.frontend_seq:
            extras["embeds"] = jax.random.normal(
                key, (B, cfg.frontend_seq, cfg.frontend_dim))
        if cfg.encoder_layers:
            extras["enc_frames"] = jax.random.normal(
                key, (B, cfg.encoder_seq, cfg.frontend_dim))
        step = make_train_step(cfg, opt)
        state, m = jax.jit(lambda s, t, l: step(s, t, l, extras or None))(
            state, toks[:, :-1], toks[:, 1:])
        assert np.isfinite(float(m["loss"])), f"{arch}: loss not finite"
        assert float(m["grad_norm"]) > 0
        for leaf in jax.tree_util.tree_leaves(state.params):
            assert np.isfinite(np.asarray(leaf, np.float32)).all(), \
                f"{arch}: non-finite params after step"

    def test_decode_step(self, arch):
        cfg = _smoke_cfg(arch)
        if not shape_supported(cfg, "long_500k") and cfg.arch_type == "audio":
            pass  # decode_32k still supported for whisper
        key = jax.random.PRNGKey(2)
        params = init_params(key, cfg)
        cache = init_cache(cfg, 2, 32)
        if cfg.encoder_layers:
            frames = jax.random.normal(key, (2, cfg.encoder_seq,
                                             cfg.frontend_dim))
            _, _, cache = forward(params, cfg, jnp.zeros((2, 1), jnp.int32),
                                  enc_frames=frames, cache=cache)
        tok = jnp.zeros((2, 1), jnp.int32)
        lg, _, cache = forward(params, cfg, tok, cache=cache)
        assert lg.shape == (2, 1, cfg.padded_vocab)
        assert np.isfinite(np.asarray(lg, np.float32)).all()


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if a not in ("whisper-base",
                                               "llava-next-34b")])
def test_straggler_round_on_reduced_arch(arch):
    """One full scheduling round (n=4, r=2, k=3, SS) per reduced text arch."""
    cfg = _smoke_cfg(arch)
    opt = adamw(1e-3)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    spec = RoundConfig(n=4, r=2, k=3, kind="ss")
    part = TaskPartition(n=4, global_batch=4, seq_len=16,
                         vocab=cfg.vocab_size)
    step = jax.jit(make_straggler_train_step(cfg, opt, spec, scenario1()))
    toks, labs = lm_task_batches(part, spec.to_matrix(), 0)
    state, m, _ = step(state, toks, labs, jax.random.PRNGKey(3))
    assert np.isfinite(float(m["loss"]))
    assert int(m["winners"]) == 3
    assert float(m["completion_time"]) > 0


def test_full_configs_match_assignment():
    """The full (non-smoke) configs carry the exact assigned dimensions."""
    rows = {
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
        "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
    }
    for arch, (L, d, H, kv, ff, V) in rows.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == H, arch
        assert cfg.n_kv_heads == kv, arch
        ff_actual = cfg.d_ff_expert if arch == "deepseek-v3-671b" else cfg.d_ff
        assert ff_actual == ff, arch
        assert cfg.vocab_size == V, arch
    # MoE details
    ds = get_config("deepseek-v3-671b")
    assert ds.n_experts == 256 and ds.experts_per_token == 8
    assert ds.n_shared_experts == 1
    jm = get_config("jamba-v0.1-52b")
    assert jm.n_experts == 16 and jm.experts_per_token == 2
    l4 = get_config("llama4-maverick-400b-a17b")
    assert l4.n_experts == 128 and l4.experts_per_token == 1
    # layer-pattern sanity on full configs
    sp = layer_specs(jm)
    assert sum(s.mixer == "gqa" for s in sp) == 4      # 1:7 in 32 layers
    assert sum(s.ffn == "moe" for s in sp) == 16       # every other layer
    sp = layer_specs(get_config("gemma3-4b"))
    assert sum(s.mixer == "swa" for s in sp) > sum(s.mixer == "gqa"
                                                   for s in sp)
    sp = layer_specs(ds)
    assert sum(s.ffn == "moe" for s in sp) == 58       # 61 - 3 dense prefix


def test_input_specs_cover_all_supported_combos():
    from repro.configs import resolve
    count = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            if not shape_supported(cfg, shape):
                assert arch == "whisper-base" and shape == "long_500k"
                continue
            rcfg = resolve(cfg, shape)
            spec = input_specs(rcfg, shape, n=16, r=1)
            assert all(hasattr(v, "shape") for v in spec.values())
            count += 1
    assert count == 39


def test_long_variant_semantics():
    qw = get_config("qwen2-72b")
    lv = long_variant(qw)
    assert lv.sliding_window == 8192
    assert all(s.mixer == "swa" for s in layer_specs(lv))
    ds = long_variant(get_config("deepseek-v3-671b"))
    assert ds.kv_lora_rank == 512      # unchanged: MLA compressed cache
    rw = long_variant(get_config("rwkv6-1.6b"))
    assert rw.ssm_kind == "rwkv6"
