import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable (e)) + roofline terms (deliverable (g)).

For every (architecture x input shape x mesh):
  * builds the jitted step (straggler train round / prefill / decode) with
    explicit in_shardings from launch.shardings,
  * ``.lower().compile()`` against ShapeDtypeStruct inputs (no allocation),
  * records ``memory_analysis()`` (fits-per-device proof),
    ``cost_analysis()`` (per-device FLOPs/bytes — XLA reports the
    partitioned per-device module), and the collective-bytes breakdown
    parsed from the compiled HLO,
  * derives the three roofline terms (TPU v5e: 197 TFLOP/s bf16, 819 GB/s
    HBM, ~50 GB/s/link ICI) and the MODEL_FLOPS/HLO_FLOPs ratio.

Layer scans are UNROLLED here (cfg.scan_layers=False): XLA's HLO cost
analysis counts while-loop bodies once, so scanned models would under-
report FLOPs by ~n_layers x. The inner SSM *time* scans remain loops —
their in-loop FLOPs (~1% of a layer's projections) are noted as a known
undercount in EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod]    # subprocess per combo
"""
import argparse
import dataclasses
import json
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import (ARCH_IDS, SHAPES, get_config, input_specs, resolve,
                       shape_supported)
from ..core import RoundConfig, scenario1
from ..models import active_params, forward, init_cache, init_params
from ..optim import adamw
from ..sharding import MeshCtx, mesh_context
from ..train import TrainState, init_train_state, make_serve_step, \
    make_straggler_train_step
from .mesh import make_mesh_ctx
from .shardings import (batch_shardings, cache_shardings, params_shardings,
                        zero1_shardings)

VARIANTS = ("zero1", "absorb", "grouped", "batchshard", "puredp",
            "ringdecode")

# --- TPU v5e hardware constants (per chip) ---------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_OP_RE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def collective_bytes(hlo: str) -> dict:
    """Sum result-shape bytes of every collective op in the (per-device)
    compiled HLO. Handles TUPLE-shaped collectives — XLA fuses many
    gradient reductions into one `(f32[..], f32[..], ...) all-reduce` —
    by summing every shape on the LHS. ``-done`` ops are skipped (their
    ``-start`` carries the shape)."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for line in hlo.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_OP_RE.search(line)
        if not m:
            continue
        lhs, op, _start = m.groups()
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(lhs):
            if dt not in _DTYPE_BYTES:
                continue
            b = _DTYPE_BYTES[dt]
            for d in dims.split(","):
                if d:
                    b *= int(d)
            nbytes += b
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _cfg_for_dryrun(arch: str, shape: str, *, scan_layers: bool = False):
    cfg = resolve(get_config(arch), shape)
    return dataclasses.replace(cfg, scan_layers=scan_layers,
                               remat=SHAPES[shape].kind == "train")


def _probe_layout(cfg):
    """(L1, L2, reps_equiv): probe layer counts for the per-period linear
    cost model F(L) = base + n_periods * per_period (see module docstring).
    """
    from ..models.config import find_period, layer_specs as _ls
    specs = _ls(cfg)
    body = specs[cfg.dense_prefix:]
    p, _ = find_period(body)
    p = min(p, len(body))
    L1 = cfg.dense_prefix + p
    L2 = cfg.dense_prefix + 2 * p
    reps_equiv = (cfg.n_layers - L1) / p
    return L1, L2, reps_equiv


def _replicated(ctx, tree):
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(ctx.mesh, P(*([None] * len(l.shape)))), tree)


def build_train(cfg, shape: str, ctx: MeshCtx, *, r: int, k_frac: float,
                schedule: str, zero1: bool = False):
    n = ctx.data_size
    k = max(1, int(round(k_frac * n)))
    spec = RoundConfig(n=n, k=k, kind=schedule, r=r)
    opt = adamw(1e-4)
    step = make_straggler_train_step(cfg, opt, spec, scenario1(),
                                     scan_slots=False)
    ins = input_specs(cfg, shape, n=n, r=r)
    state_shapes = jax.eval_shape(
        lambda key: init_train_state(key, cfg, opt), jax.random.PRNGKey(0))
    fallbacks: list = []
    psh = params_shardings(state_shapes.params, ctx, fallbacks)
    osh = psh
    if zero1:
        osh = zero1_shardings(state_shapes.params, psh, ctx)
    state_sh = TrainState(
        params=psh,
        opt_state={"step": NamedSharding(ctx.mesh, P()),
                   "m": osh, "v": osh},
        step=NamedSharding(ctx.mesh, P()))
    tok_sh = batch_shardings(
        {"t": ins["slot_tokens"], "l": ins["slot_labels"]}, ctx,
        slot_major=True)
    extras_shapes = {}
    extras_sh = {}
    if "slot_embeds" in ins:
        extras_shapes["embeds"] = ins["slot_embeds"]
    if "slot_frames" in ins:
        extras_shapes["enc_frames"] = ins["slot_frames"]
    if extras_shapes:
        extras_sh = batch_shardings(extras_shapes, ctx, slot_major=True)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    rng_sh = NamedSharding(ctx.mesh, P(None))

    def fn(state, toks, labs, rng, extras):
        new_state, metrics, _cluster = step(state, toks, labs, rng,
                                            extras=extras or None)
        return new_state, metrics

    jitted = jax.jit(fn, in_shardings=(state_sh, tok_sh["t"], tok_sh["l"],
                                       rng_sh, extras_sh),
                     donate_argnums=(0,))
    args = (state_shapes, ins["slot_tokens"], ins["slot_labels"], rng,
            extras_shapes)
    meta = {"round": dict(n=n, r=r, k=k, schedule=schedule),
            "fallbacks": [str(f) for f in fallbacks]}
    return jitted, args, meta


def build_prefill(cfg, shape: str, ctx: MeshCtx):
    ins = input_specs(cfg, shape)
    fallbacks: list = []
    params_shapes = jax.eval_shape(lambda key: init_params(key, cfg),
                                   jax.random.PRNGKey(0))
    psh = params_shardings(params_shapes, ctx, fallbacks)
    bsh = batch_shardings(ins, ctx)

    def fn(params, batch):
        logits, _, _ = forward(params, cfg, batch["tokens"],
                               embeds=batch.get("embeds"),
                               enc_frames=batch.get("enc_frames"))
        return jnp.argmax(logits[:, -1], axis=-1)

    jitted = jax.jit(fn, in_shardings=(psh, bsh))
    meta = {"fallbacks": [str(f) for f in fallbacks]}
    return jitted, (params_shapes, ins), meta


def build_decode(cfg, shape: str, ctx: MeshCtx):
    sh = SHAPES[shape]
    B, S = sh.global_batch, sh.seq_len
    ins = input_specs(cfg, shape)
    fallbacks: list = []
    params_shapes = jax.eval_shape(lambda key: init_params(key, cfg),
                                   jax.random.PRNGKey(0))
    psh = params_shardings(params_shapes, ctx, fallbacks)
    cache_shapes = jax.eval_shape(lambda: init_cache(cfg, B, S))
    csh = cache_shardings(cache_shapes, ctx, fallbacks)
    tok_sh = batch_shardings(ins, ctx)
    serve = make_serve_step(cfg)

    def fn(params, cache, tokens):
        return serve(params, cache, tokens)

    jitted = jax.jit(fn, in_shardings=(psh, csh, tok_sh["tokens"]),
                     donate_argnums=(1,))
    meta = {"fallbacks": [str(f) for f in fallbacks]}
    return jitted, (params_shapes, cache_shapes, ins["tokens"]), meta


def model_flops_global(cfg, shape: str, *, r: int = 1) -> float:
    """Useful MODEL_FLOPS for the step: 6*N_active*D train (x r redundancy
    excluded — that's the *useful* figure), 2*N*D prefill, 2*N*B decode."""
    sh = SHAPES[shape]
    N = active_params(cfg)
    if sh.kind == "train":
        return 6.0 * N * sh.global_batch * sh.seq_len
    if sh.kind == "prefill":
        return 2.0 * N * sh.global_batch * sh.seq_len
    return 2.0 * N * sh.global_batch


def _build_and_compile(cfg, shape, ctx, *, kind, r, k_frac, schedule,
                       zero1=False):
    t0 = time.time()
    with mesh_context(ctx):
        if kind == "train":
            jitted, args, meta = build_train(cfg, shape, ctx, r=r,
                                             k_frac=k_frac,
                                             schedule=schedule,
                                             zero1=zero1)
        elif kind == "prefill":
            jitted, args, meta = build_prefill(cfg, shape, ctx)
        else:
            jitted, args, meta = build_decode(cfg, shape, ctx)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
               if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}
    coll = collective_bytes(compiled.as_text())
    return {"flops": flops, "bytes": bytes_acc, "coll": coll, "mem": mem,
            "meta": meta, "t_lower": t_lower, "t_compile": t_compile}


# Probe-extrapolated accounting for deep train/prefill graphs: unrolling 80
# layers is exact but takes tens of minutes of XLA CPU compile per combo.
# Instead: (1) the FULL config is compiled in scan-over-layers mode — this
# is the deployable program and is the compile-proof + memory_analysis
# artifact; (2) two small UNROLLED probes (dense_prefix + 1 period, + 2
# periods) give per-period FLOPs/bytes/collectives exactly, and the linear
# model F(L) = base + n_periods*per_period extrapolates to the full depth.
# Exact for every arch whose depth is an integral number of periods (all
# but gemma3's 4-layer tail, ~2% overcount of its global-attn share).
PROBE_LAYER_THRESHOLD = 16


def run_one(arch: str, shape: str, *, multi_pod: bool, r: int = 1,
            k_frac: float = 1.0, schedule: str = "ss",
            out_dir: str = "experiments/dryrun", tag: str = "",
            exact: bool = False, variant: str = "") -> dict:
    cfg0 = get_config(arch)
    if not shape_supported(cfg0, shape):
        return {"arch": arch, "shape": shape, "skipped": True,
                "reason": "DESIGN.md §5 skip (whisper long_500k)"}
    ctx = make_mesh_ctx(multi_pod=multi_pod)
    n_dev = ctx.mesh.size
    kind = SHAPES[shape].kind
    cfg = _cfg_for_dryrun(arch, shape)
    overrides = {}
    zero1 = False
    for v in filter(None, variant.split(",")):
        if v == "zero1":
            zero1 = True
        elif v == "absorb":
            overrides["mla_absorb"] = True
        elif v == "grouped":
            overrides["grouped_gqa"] = True
        elif v == "batchshard":
            overrides["attn_batch_shard_fallback"] = True
        elif v == "ringdecode":
            overrides["seq_shard_decode"] = True
        elif v == "puredp":
            # tiny-model deployment choice: no tensor-parallel axis — the
            # whole mesh becomes data parallelism (params replicated)
            ctx = MeshCtx(mesh=ctx.mesh,
                          data_axes=tuple(ctx.data_axes) +
                          (ctx.model_axis,),
                          model_axis=None)
        else:
            raise ValueError(f"unknown variant {v!r}; have {VARIANTS}")
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if variant and not tag:
        tag = variant.replace(",", "+")
    use_probe = (not exact and kind in ("train", "prefill")
                 and cfg.n_layers > PROBE_LAYER_THRESHOLD)
    bc = dict(kind=kind, r=r, k_frac=k_frac, schedule=schedule,
              zero1=zero1)
    if use_probe:
        # full-config compile proof + memory, scanned (deployable form)
        full = _build_and_compile(
            dataclasses.replace(_cfg_for_dryrun(arch, shape,
                                                scan_layers=True),
                                **overrides),
            shape, ctx, **bc)
        L1, L2, reps_equiv = _probe_layout(cfg)
        p1 = _build_and_compile(
            dataclasses.replace(cfg, n_layers=L1), shape, ctx, **bc)
        p2 = _build_and_compile(
            dataclasses.replace(cfg, n_layers=L2), shape, ctx, **bc)
        flops = p1["flops"] + (p2["flops"] - p1["flops"]) * reps_equiv
        bytes_acc = p1["bytes"] + (p2["bytes"] - p1["bytes"]) * reps_equiv
        coll = {"bytes": {}, "counts": {}, "total_bytes": 0}
        for op in p1["coll"]["bytes"]:
            b = p1["coll"]["bytes"][op] + (p2["coll"]["bytes"][op] -
                                           p1["coll"]["bytes"][op]
                                           ) * reps_equiv
            coll["bytes"][op] = int(max(b, 0))
            coll["counts"][op] = p1["coll"]["counts"][op]
        coll["total_bytes"] = int(sum(coll["bytes"].values()))
        mem = full["mem"]
        meta = full["meta"]
        meta["accounting"] = (f"scan-compile + probe-extrapolated "
                              f"(L1={L1}, L2={L2}, "
                              f"reps_equiv={reps_equiv:.3f})")
        t_lower = full["t_lower"] + p1["t_lower"] + p2["t_lower"]
        t_compile = full["t_compile"] + p1["t_compile"] + p2["t_compile"]
    else:
        res = _build_and_compile(cfg, shape, ctx, **bc)
        flops, bytes_acc = res["flops"], res["bytes"]
        coll, mem, meta = res["coll"], res["mem"], res["meta"]
        meta["accounting"] = "unrolled-exact"
        t_lower, t_compile = res["t_lower"], res["t_compile"]

    compute_t = flops / PEAK_FLOPS
    memory_t = bytes_acc / HBM_BW
    coll_t = coll["total_bytes"] / LINK_BW
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    dominant = max(terms, key=terms.get)
    mf = model_flops_global(cfg, shape, r=r)
    mf_per_dev = mf / n_dev
    result = {
        "arch": arch, "shape": shape, "mesh": "2x16x16" if multi_pod
        else "16x16", "n_devices": n_dev, "kind": kind,
        "variant": variant or "baseline",
        "round_r": r, "round_k_frac": k_frac,
        "config_name": cfg.name,
        "active_params": active_params(cfg),
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collectives": coll,
        "memory_analysis": mem,
        "roofline": {**terms, "dominant": dominant,
                     "model_flops_global": mf,
                     "model_flops_per_device": mf_per_dev,
                     "useful_ratio": (mf_per_dev / flops) if flops else 0.0},
        "meta": meta,
        "timings": {"lower_s": t_lower, "compile_s": t_compile},
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fname = (f"{out_dir}/{'multipod' if multi_pod else 'pod'}__"
                 f"{arch}__{shape}{suffix}.json")
        with open(fname, "w") as f:
            json.dump(result, f, indent=1)
        result["file"] = fname
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every supported combo in subprocesses")
    ap.add_argument("--r", type=int, default=1, help="computation load")
    ap.add_argument("--k-frac", type=float, default=1.0,
                    help="computation target as fraction of n")
    ap.add_argument("--schedule", default="ss")
    ap.add_argument("--variant", default="",
                    help="comma list of " + ",".join(VARIANTS))
    ap.add_argument("--exact", action="store_true",
                    help="force unrolled-exact accounting (slow)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--timeout", type=int, default=3000)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    if args.all:
        failures = []
        for arch in ARCH_IDS:
            for shape in SHAPES:
                if not shape_supported(get_config(arch), shape):
                    print(f"SKIP {arch} {shape} (DESIGN.md §5)")
                    continue
                suffix = f"__{args.tag}" if args.tag else ""
                fname = (f"{args.out_dir}/"
                         f"{'multipod' if args.multi_pod else 'pod'}__"
                         f"{arch}__{shape}{suffix}.json")
                if args.skip_existing and os.path.exists(fname):
                    print(f"EXISTS {fname}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--r", str(args.r), "--k-frac", str(args.k_frac),
                       "--schedule", args.schedule,
                       "--out-dir", args.out_dir]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                if args.tag:
                    cmd += ["--tag", args.tag]
                print(f"=== {arch} {shape} "
                      f"{'multipod' if args.multi_pod else 'pod'} ===",
                      flush=True)
                rc = subprocess.run(cmd, timeout=args.timeout).returncode
                if rc != 0:
                    failures.append((arch, shape))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("ALL DRY-RUNS PASSED")
        return

    res = run_one(args.arch, args.shape, multi_pod=args.multi_pod,
                  r=args.r, k_frac=args.k_frac, schedule=args.schedule,
                  out_dir=args.out_dir, tag=args.tag, exact=args.exact,
                  variant=args.variant)
    print(json.dumps(
        {k: res[k] for k in res if k not in ("meta",)}, indent=1,
        default=str))


if __name__ == "__main__":
    main()
