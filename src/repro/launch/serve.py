"""Serving launcher: batched greedy decode with KV cache for any --arch
(``--smoke`` for CPU). Demonstrates prefill -> decode on the public API.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
      --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..models import forward, init_cache, init_params
from ..train import make_serve_step
from ..compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        if cfg.arch_type == "hybrid":
            cfg = dataclasses.replace(cfg, ssm_period=2, ssm_attn_offset=1)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    B = args.batch
    max_len = args.prompt_len + args.gen + 8
    cache = init_cache(cfg, B, max_len)
    prompt = jax.random.randint(key, (B, args.prompt_len), 0,
                                cfg.vocab_size)
    kwargs = {}
    if cfg.encoder_layers:
        kwargs["enc_frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.frontend_dim))

    t0 = time.time()
    logits, _, cache = forward(params, cfg, prompt, cache=cache, **kwargs)
    nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t_prefill = time.time() - t0
    serve = jax.jit(make_serve_step(cfg))
    out = [nxt]
    t0 = time.time()
    for _ in range(args.gen - 1):
        nxt, cache = serve(params, cache, nxt)
        out.append(nxt)
    t_dec = time.time() - t0
    toks = np.concatenate([np.asarray(t) for t in out], axis=1)
    print(f"{cfg.name}: prefill {args.prompt_len} tok in "
          f"{t_prefill * 1e3:.1f} ms; {args.gen - 1} decode steps in "
          f"{t_dec * 1e3:.1f} ms ({(args.gen - 1) * B / max(t_dec, 1e-9):.1f}"
          f" tok/s batch={B})")
    for b in range(min(B, 2)):
        print(f"  req{b}: {toks[b, :16].tolist()}...")


if __name__ == "__main__":
    main()
