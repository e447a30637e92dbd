"""Launch-layer tests: mesh builders, sharding rules, HLO collective
parser, dry-run plumbing on a tiny local mesh."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.shardings import param_spec
from repro.launch.dryrun import collective_bytes, model_flops_global
from repro.launch.mesh import make_local_mesh_ctx
from repro.sharding import MeshCtx, mesh_context, shard
from repro.models import ModelConfig, init_params, forward
from repro.configs import get_config, SHAPES


class TestCollectiveParser:
    HLO = """
  %p = f32[8,16]{1,0} parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %p), replica_groups={}
  %ag = bf16[4,256]{1,0} all-gather(bf16[4,64]{1,0} %x), dimensions={1}
  %rs = f32[2,8]{1,0} reduce-scatter(f32[16,8]{1,0} %y), dimensions={0}
  %a2a = f32[16]{0} all-to-all(f32[16]{0} %z)
  %cp = u32[4]{0} collective-permute(u32[4]{0} %w)
  %ards = f32[8,16]{1,0} all-reduce-start(f32[8,16]{1,0} %p)
  %ardd = f32[8,16]{1,0} all-reduce-done(f32[8,16]{1,0} %ards)
"""

    def test_bytes_and_counts(self):
        res = collective_bytes(self.HLO)
        assert res["bytes"]["all-reduce"] == 8 * 16 * 4 * 2  # ar + ar-start
        assert res["bytes"]["all-gather"] == 4 * 256 * 2
        assert res["bytes"]["reduce-scatter"] == 2 * 8 * 4
        assert res["bytes"]["all-to-all"] == 16 * 4
        assert res["bytes"]["collective-permute"] == 4 * 4
        assert res["counts"]["all-reduce"] == 2
        assert res["total_bytes"] == sum(res["bytes"].values())

    def test_done_ops_not_double_counted(self):
        res = collective_bytes(self.HLO)
        # -done skipped; -start counted once
        assert res["counts"]["all-reduce"] == 2


class TestParamSpecRules:
    def _ctx(self):
        # fabricate a ctx with model_size 4 over actual devices=1: use mesh
        # of 1x1 but override sizes via a stub
        class Stub:
            model_axis = "model"
            model_size = 4
            data_axes = ("data",)
        return Stub()

    @pytest.mark.parametrize("path,shape,want", [
        ("embed", (512, 64), P("model", None)),
        ("lm_head/w", (64, 512), P(None, "model")),
        ("segments/0/0/mixer/wq/w", (64, 128), P(None, "model")),
        ("segments/0/0/mixer/wo/w", (128, 64), P("model", None)),
        ("segments/0/0/ffn/w_gate/w", (64, 256), P(None, "model")),
        ("segments/0/0/ffn/w_down/w", (256, 64), P("model", None)),
        ("segments/0/0/ffn/w_gate", (8, 64, 32), P("model", None, None)),
        ("segments/0/0/ffn/router", (64, 8), P(None, None)),
        ("segments/0/0/norm1/scale", (64,), P(None)),
        ("segments/0/0/mixer/in_proj/w", (64, 256), P(None, "model")),
        ("segments/0/0/mixer/out_proj/w", (128, 64), P("model", None)),
        # divisibility fallback: 6 not divisible by 4
        ("segments/0/0/mixer/wq/w", (64, 6), P(None, None)),
    ])
    def test_rules(self, path, shape, want):
        fb = []
        got = param_spec(path, shape, self._ctx(), fb)
        assert tuple(got) == tuple(want), (path, got, want)

    def test_fallback_recorded(self):
        fb = []
        param_spec("segments/0/0/mixer/wq/w", (64, 6), self._ctx(), fb)
        assert len(fb) == 1


class TestLocalMeshForward:
    """Tiny model under a real (1x1) mesh context: sharding constraints and
    the MoE shard_map path must still produce identical numerics."""

    def test_forward_matches_no_mesh(self):
        cfg = ModelConfig(name="m", arch_type="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                          n_experts=4, experts_per_token=2, d_ff_expert=64,
                          capacity_factor=8.0, param_dtype="float32",
                          dtype="float32", remat=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 128)
        base, _, _ = forward(params, cfg, toks)
        ctx = make_local_mesh_ctx(1, 1)
        with mesh_context(ctx):
            meshy, _, _ = forward(params, cfg, toks)
        np.testing.assert_allclose(np.asarray(base), np.asarray(meshy),
                                   rtol=1e-5, atol=1e-5)


class TestModelFlops:
    def test_kind_scaling(self):
        cfg = get_config("phi4-mini-3.8b")
        t = model_flops_global(cfg, "train_4k")
        p = model_flops_global(cfg, "prefill_32k")
        d = model_flops_global(cfg, "decode_32k")
        # train: 6*N*256*4096; prefill: 2*N*32*32768; decode: 2*N*128
        assert t / p == pytest.approx(3.0, rel=1e-6)
        assert d < p < t


def test_shard_noop_without_mesh():
    x = jnp.ones((4, 8))
    from repro.sharding import DATA, MODEL
    y = shard(x, DATA, MODEL)
    assert y is x


class TestCompileCache:
    """``enable_compile_cache`` places JAX's persistent cache from outside:
    ``JAX_COMPILATION_CACHE_DIR`` when set (the code sets no other
    directory), else the fixed ``.jax_cache`` at the checkout root."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_defaults_to_checkout_root(self, monkeypatch):
        from pathlib import Path
        from repro import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable_compile_cache()
        root = Path(__file__).resolve().parents[1]
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path

    def test_environment_variable_wins(self, monkeypatch, tmp_path):
        from repro import compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
