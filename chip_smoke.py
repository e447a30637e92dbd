"""Smoke run of the main path on a TPU, through the entry points users call.

    python chip_smoke.py             # phases 0-5 on one chip
    python chip_smoke.py --chips 4   # trial sharding over four chips only

Phases (one process holds the chip for all of them):

  0. device  -- a TPU must be attached; without one the script exits
     non-zero before any phase runs.
  1. sweep   -- the paper's single-round engine at n = k = 16 against the
     coded schemes' closed form, a plain NumPy Monte-Carlo, and the
     oracle lower bound.
  2. rounds  -- adaptive ``sweep_rounds`` (re-balancing, censored
     feedback): the Pallas greedy kernel, compiled, against its scan twin.
  3. kernel  -- the paper's Sec. VI ``h(X) = X X^T theta`` Pallas kernel
     against a float32 reference.
  4. train   -- the straggler train step of phi4-mini-3.8b at its published
     widths, cut to 2 layers and an eighth of the vocabulary.
  5. live    -- the live master/worker runtime against a replay of its own
     recorded trace.

With ``--chips 4`` only the sharded phase runs: ``devices=4`` against
``devices=1`` for a sweep and an adaptive rounds sweep, bit for bit.

Each phase prints its checks and numbers on lines of its own; a failed
check raises, so the script exits non-zero.  Step times, memory and
compile seconds are this script's own readings, not benchmark metrics.
The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

N, K = 16, 16                       # the paper's Fig.-4 corner
LOADS = (4, 16)
SWEEP_TRIALS = 1_000_000
SWEEP_CHUNK = 262_144   # ~4.3 GB per chunk by the v5e compile: a quarter of HBM
REF_TRIALS = 100_000
ROUNDS, ROUND_TRIALS, ROUND_CHUNK, ROUND_K = 20, 8192, 2048, 12
LIVE_ROUNDS = 10
# paper Sec. VI-C, scenario 1 (eq. 66): per-slot compute and per-result
# communication delays, N(mu, sigma^2) truncated to [mu - a, mu + a]
SCENARIO1 = dict(mu1=1e-4, sigma1=1e-4, a1=3e-5,
                 mu2=5e-4, sigma2=2e-4, a2=2e-4)


class CheckFailed(AssertionError):
    pass


def check(name: str, ok: bool, detail: str) -> None:
    print(f"  check {name}: {'PASS' if ok else 'FAIL'}  {detail}",
          flush=True)
    if not ok:
        raise CheckFailed(f"{name}: {detail}")


def require_compiled(name: str, text: str) -> None:
    check(f"{name}_compiled", "tpu_custom_call" in text,
          "the compiled program holds the Mosaic kernel (tpu_custom_call), "
          "not an interpreted loop")


def info(msg: str) -> None:
    print(f"  {msg}", flush=True)


class CompileStats:
    """Backend compile seconds and persistent-cache hits of this process,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration


def device_memory() -> dict:
    import jax
    return jax.devices()[0].memory_stats() or {}


# ------------------------------- phase 0 -------------------------------------

def phase_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devs[0].platform!r} devices only")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices; JAX found {len(devs)}")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    info(f"device {dev['kind']} x{dev['count']}  jax {jax.__version__}")
    return dev


# ------------------------------- phase 1 -------------------------------------

def _np_truncnorm(rng, shape, mu, sigma, a):
    """N(mu, sigma^2) truncated to [mu - a, mu + a], by rejection."""
    size = int(np.prod(shape))
    out = np.empty(size)
    filled = 0
    while filled < size:
        z = rng.normal(mu, sigma, 4 * (size - filled) + 1024)
        z = z[np.abs(z - mu) <= a][:size - filled]
        out[filled:filled + z.size] = z
        filled += z.size
    return out.reshape(shape)


def np_round_mean(C: np.ndarray, k: int, trials: int, seed: int,
                  chunk: int = 10_000):
    """Plain NumPy Monte-Carlo of one round of TO matrix ``C`` under
    scenario 1: slot j of worker i lands at the worker's cumulative
    compute time through slot j plus that slot's communication time; a
    task arrives with its first copy; the round closes at the k-th
    distinct task.  Returns (mean, stderr)."""
    n, r = C.shape
    p = SCENARIO1
    rng = np.random.default_rng(seed)
    done = []
    for start in range(0, trials, chunk):
        m = min(chunk, trials - start)
        T1 = _np_truncnorm(rng, (m, n, r), p["mu1"], p["sigma1"], p["a1"])
        T2 = _np_truncnorm(rng, (m, n, r), p["mu2"], p["sigma2"], p["a2"])
        slot = np.cumsum(T1, axis=2) + T2
        task = np.full((m, n), np.inf)
        for i in range(n):
            for j in range(r):
                task[:, C[i, j]] = np.minimum(task[:, C[i, j]], slot[:, i, j])
        done.append(np.partition(task, k - 1, axis=1)[:, k - 1])
    x = np.concatenate(done)
    return float(x.mean()), float(x.std(ddof=1) / math.sqrt(trials))


def sweep_specs(loads=LOADS):
    from repro.core import (cyclic_to_matrix, lb_spec, pc_spec, pcmm_spec,
                            random_assignment_to_matrix, staircase_to_matrix,
                            to_spec)
    specs = []
    for r in loads:
        specs += [to_spec(f"cs{r}", cyclic_to_matrix(N, r)),
                  to_spec(f"ss{r}", staircase_to_matrix(N, r)),
                  pc_spec(r, name=f"pc{r}"), pcmm_spec(r, name=f"pcmm{r}"),
                  lb_spec(r, name=f"lb{r}")]
        if r == N:
            specs.append(to_spec(f"ra{r}",
                                 random_assignment_to_matrix(N, seed=0)))
    return specs


def phase_sweep():
    from repro.core import (delay_model_pdfs, multimessage_coded_mean,
                            pc_threshold, scenario1, sweep)
    model = scenario1()
    check("scenario1_params",
          all(getattr(model, f) == v for f, v in SCENARIO1.items()),
          "engine model matches the reference's constants")
    specs = sweep_specs()
    info(f"n={N} k={K} loads={LOADS} trials={SWEEP_TRIALS} "
         f"chunk={SWEEP_CHUNK} "
         f"schemes={[sp.name for sp in specs]}")
    t0 = time.perf_counter()
    res = sweep(specs, model, N, trials=SWEEP_TRIALS, chunk=SWEEP_CHUNK,
                ks=K, seed=0)
    wall = time.perf_counter() - t0
    mem = device_memory()
    info(f"sweep wall {wall:.3f}s (compile included); device peak "
         f"{mem.get('peak_bytes_in_use', 0)} of "
         f"{mem.get('bytes_limit', 0)} bytes")
    mean = {sp.name: res.at_k(sp.name, K) for sp in specs}
    se = {sp.name: float(np.ravel(res.stderr[sp.name])[-1]) for sp in specs}
    for nm in mean:
        info(f"{nm:7s} mean {mean[nm]:.9e} stderr {se[nm]:.3e}")
    check("finite", all(np.isfinite(v) and v > 0 for v in mean.values()),
          "every scheme's mean is finite and positive")

    # 1. coded schemes vs the closed form (theory.multimessage_coded_mean).
    # The closed form integrates discretized densities; its integration
    # error is taken as its spread over three grid sizes.  PCMM's form
    # assumes in-order delivery within a worker, exact for PC.
    pdf1, pdf2, sup1, sup2 = delay_model_pdfs(model)
    for r in LOADS:
        tmax = r * sup1 + sup2                # the latest possible arrival
        for nm, msgs, th in ((f"pc{r}", 1, (pc_threshold(N, r) - 1) * r + 1),
                             (f"pcmm{r}", r, None)):
            cf = [multimessage_coded_mean(N, r, msgs, pdf1, pdf2, tmax=tmax,
                                          npts=p, threshold=th)
                  for p in (2048, 4096, 8192)]
            integ = max(abs(c - cf[-1]) for c in cf)
            gap = abs(mean[nm] - cf[-1])
            check(f"closed_form_{nm}", gap <= 4 * se[nm] + integ,
                  f"|mc - cf| = {gap:.3e} <= 4*{se[nm]:.3e} + "
                  f"{integ:.3e} (cf {cf[-1]:.9e})")

    # 2. uncoded schemes vs an independent NumPy Monte-Carlo
    t0 = time.perf_counter()
    by_name = {sp.name: sp for sp in specs}
    for nm in [f"cs{r}" for r in LOADS] + [f"ss{r}" for r in LOADS] + \
            [f"ra{N}"]:
        C = by_name[nm].matrix()
        ref, ref_se = np_round_mean(C, K, REF_TRIALS, seed=1)
        tol = 4 * math.hypot(se[nm], ref_se)
        check(f"numpy_ref_{nm}", abs(mean[nm] - ref) <= tol,
              f"engine {mean[nm]:.9e} vs numpy {ref:.9e} "
              f"(|diff| {abs(mean[nm] - ref):.3e} <= {tol:.3e})")
    info(f"numpy reference {REF_TRIALS} trials x 5 schemes in "
         f"{time.perf_counter() - t0:.1f}s (host)")

    # 3. the oracle lower bound is below every uncoded scheme at its load
    for r in LOADS:
        uncoded = [f"cs{r}", f"ss{r}"] + ([f"ra{r}"] if r == N else [])
        check(f"lower_bound_r{r}",
              all(mean[f"lb{r}"] <= mean[u] for u in uncoded),
              f"lb{r} {mean[f'lb{r}']:.6e} <= "
              + ", ".join(f"{u} {mean[u]:.6e}" for u in uncoded))


# ------------------------------- phase 2 -------------------------------------

def rounds_specs():
    from repro.core import (adaptive_spec, cyclic_to_matrix, lb_spec,
                            to_spec)
    return [to_spec("cs", cyclic_to_matrix(N, 4)),
            adaptive_spec("adapt", cyclic_to_matrix(N, 4)),
            adaptive_spec("rebal", cyclic_to_matrix(N, 8), loads=(4,) * N,
                          rebalance=True),
            lb_spec(4)]


def run_rounds(greedy_impl=None, devices=None):
    from repro.core import ec2_cluster, sweep_rounds
    return sweep_rounds(rounds_specs(), ec2_cluster(N, persistence=0.95),
                        N, rounds=ROUNDS, k=ROUND_K, trials=ROUND_TRIALS,
                        chunk=ROUND_CHUNK, seed=0, censored_feedback=True,
                        greedy_impl=greedy_impl, devices=devices)


def same_rounds(a, b) -> bool:
    return all(np.array_equal(getattr(a, f)[nm], getattr(b, f)[nm])
               for f in ("per_round", "stderr", "wallclock")
               for nm in a.per_round)


def greedy_lowered_text() -> str:
    """The greedy call the rounds scan makes for one chunk (default
    implementation), compiled for the attached backend."""
    import jax
    import jax.numpy as jnp
    from repro.core import cyclic_to_matrix, greedy_row_assignment_batch
    C = cyclic_to_matrix(N, 4)
    fn = jax.jit(lambda est: greedy_row_assignment_batch(C, est))
    est = jax.ShapeDtypeStruct((ROUND_CHUNK, N), jnp.float32)
    return fn.lower(est).compile().as_text()


def phase_rounds():
    info(f"n={N} k={ROUND_K} rounds={ROUNDS} trials={ROUND_TRIALS} "
         f"chunk={ROUND_CHUNK} "
         f"ec2_cluster(persistence=0.95), censored feedback")
    out = {}
    for impl in ("kernel", "scan"):
        t0 = time.perf_counter()
        out[impl] = run_rounds(impl)
        info(f"greedy_impl={impl}: wall {time.perf_counter() - t0:.3f}s "
             f"(compile included)")
    res = out["kernel"]
    for nm in res.per_round:
        info(f"{nm:6s} mean round {res.mean_round(nm):.9e}")
    check("finite", all(np.isfinite(v).all() and (v > 0).all()
                        for v in res.per_round.values()),
          "every per-round mean is finite and positive")
    check("kernel_equals_scan", same_rounds(out["kernel"], out["scan"]),
          "per-round means, stderrs and wall-clock curves bit-identical")
    require_compiled("greedy_assign", greedy_lowered_text())


# ------------------------------- phase 3 -------------------------------------

def gram_cases():
    """(label, Xs (tasks, d, b)) at the paper's regression size (N = 900
    samples, d = 400 features over n = 15 tasks) and one 4096^2 block."""
    import jax
    from repro.configs.paper_regression import config
    from repro.data import regression_dataset, regression_tasks
    rc = config()
    X, y, _ = regression_dataset(jax.random.PRNGKey(0), rc.N, rc.d)
    Xs, _ = regression_tasks(X, y, rc.n)
    big = jax.random.normal(jax.random.PRNGKey(1), (1, 4096, 4096))
    return [(f"paper_regression N={rc.N} d={rc.d} n={rc.n}",
             Xs.transpose(0, 2, 1)), ("block 4096x4096", big)]


def gram_lowered_text() -> str:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import batched_gram_matvec
    xs = jax.ShapeDtypeStruct((15, 400, 60), jnp.float32)
    th = jax.ShapeDtypeStruct((400,), jnp.float32)
    return batched_gram_matvec.lower(xs, th).compile().as_text()


def phase_kernel():
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import batched_gram_matvec
    for label, Xs in gram_cases():
        theta = jax.random.normal(jax.random.PRNGKey(2), (Xs.shape[1],))
        got = batched_gram_matvec(Xs, theta)
        with jax.default_matmul_precision("highest"):
            want = jax.vmap(lambda X: X @ (X.T @ theta))(Xs)
        err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        # float32 sums over d + b terms stay near 1e-6 of the largest
        # output; operands rounded to bf16 on the MXU give ~3e-3.
        check(f"gram_matvec {label}", err <= 2e-4,
              f"max|kernel - ref| / max|ref| = {err:.3e} <= 2e-4")
    require_compiled("gram_matvec", gram_lowered_text())


# ------------------------------- phase 4 -------------------------------------

TRAIN_ARGS = ["--arch", "phi4-mini-3.8b", "--n", "4", "--r", "2", "--k", "3",
              "--schedule", "ss", "--adaptive", "--cluster", "markov",
              "--seq", "2048", "--batch", "4", "--steps", "10"]


def train_config():
    """phi4-mini-3.8b at every published width, cut in depth to 2 layers
    and to one eighth of the vocabulary (one chip's slice if the
    vocabulary were split over eight)."""
    from repro.configs import get_config
    full = get_config("phi4-mini-3.8b")
    return full, dataclasses.replace(full, n_layers=2,
                                     vocab_size=full.vocab_size // 8)


def _param_count(cfg) -> int:
    import jax
    from repro.models import init_params, num_params
    return num_params(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))


def phase_train():
    from repro.launch.train import (build_parser, build_run, derive_seeds,
                                    round_config, straggler_rounds)
    args = build_parser().parse_args(TRAIN_ARGS)
    full, cfg = train_config()
    at_full_vocab = dataclasses.replace(cfg, vocab_size=full.vocab_size)
    n_params = _param_count(cfg)
    info(f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads / "
         f"{cfg.n_kv_heads} kv of {cfg.head_dim}, d_ff {cfg.d_ff}")
    info(f"cut: depth {full.n_layers} -> {cfg.n_layers} layers; vocab "
         f"{full.vocab_size} -> {cfg.vocab_size} (one eighth)")
    info(f"params at {cfg.n_layers} layers: {_param_count(at_full_vocab):,} "
         f"with the full vocab, {n_params:,} with the slice (x12 B = "
         f"{12 * n_params / 1e9:.2f} GB weights, grads and Adam state)")
    seeds = derive_seeds(args.seed)
    run = build_run(args, cfg, seeds, round_config(args, seeds))
    spec = run.spec
    info(f"round n={spec.n} r={spec.r} k={spec.k} {args.schedule}+adaptive "
         f"cluster {args.cluster}; seq {args.seq}, global batch "
         f"{args.batch}, {args.steps} steps")
    losses, winners, times = [], [], []
    t0 = time.perf_counter()
    for i, _, m in straggler_rounds(run, seeds["delay_root"], args.steps):
        losses.append(float(m["loss"]))
        winners.append(int(m["winners"]))
        t1 = time.perf_counter()
        times.append(t1 - t0)
        t0 = t1
        info(f"step {i} loss {losses[-1]:.4f} winners {winners[-1]} "
             f"time {times[-1]:.3f}s")
    mem = device_memory()
    steady = times[2:]          # steps 0 and 1 compile (fresh, then carried
    #                             cluster state)
    info(f"step time after warm-up: median {np.median(steady):.3f}s "
         f"(steps 2-{args.steps - 1}, host data generation included); "
         f"device peak {mem.get('peak_bytes_in_use', 0)} bytes")
    check("finite_loss", all(np.isfinite(losses)), "every loss is finite")
    # At init the final norm gives unit-RMS features and the head has
    # std 1/sqrt(d_model), so logits are ~N(0, 1) and the expected loss is
    # E[logsumexp] ~= ln V + 1/2, not ln V.
    chance = math.log(cfg.vocab_size)
    check("initial_loss", abs(losses[0] - (chance + 0.5)) <= 0.05 * chance,
          f"step-0 loss {losses[0]:.4f} within 5% of ln V = {chance:.4f} "
          f"from ln V + 1/2 = {chance + 0.5:.4f} (V = {cfg.vocab_size})")
    check("loss_falls", losses[-1] < losses[0],
          f"last loss {losses[-1]:.4f} < first {losses[0]:.4f}")
    check("winners", all(w == spec.k for w in winners),
          f"winners == k = {spec.k} on every step: {winners}")


# ------------------------------- phase 5 -------------------------------------

def phase_live():
    from repro.core import RoundConfig, TraceProcess, ec2_cluster, scenario1
    from repro.core import sweep_rounds
    from repro.live import run_live
    cfg = RoundConfig(n=N, k=12, kind="cs", r=4, seed=7)

    def process():
        return ec2_cluster(N, spread=3.0, p_slow=0.25, persistence=0.9,
                           slow=8.0, base=scenario1(), seed=1)

    spec = cfg.to_scheme_spec("cs")
    t0 = time.perf_counter()
    res = run_live(cfg, process(), LIVE_ROUNDS, abort_on_close=False)
    wall = time.perf_counter() - t0
    info(f"run_live: {N} in-process workers, {LIVE_ROUNDS} rounds, "
         f"time_scale=0, wall {wall:.3f}s "
         f"({LIVE_ROUNDS / wall:.2f} rounds/s, compile included)")
    live = res.per_round.astype(np.float32)
    rep = sweep_rounds([spec], TraceProcess(res.trace), N,
                       rounds=LIVE_ROUNDS, trials=1, k=cfg.k, seed=cfg.seed)
    eng = sweep_rounds([spec], process(), N, rounds=LIVE_ROUNDS, trials=1,
                       k=cfg.k, seed=cfg.seed, record_trace=True)
    check("finite", bool(np.isfinite(live).all()), "every round closed")
    check("live_equals_replay",
          np.array_equal(live, rep.per_round["cs"].astype(np.float32)),
          "per-round times equal sweep_rounds(TraceProcess(trace)) bit "
          "for bit")
    check("live_equals_engine",
          np.array_equal(live, eng.per_round["cs"].astype(np.float32)),
          "per-round times equal sweep_rounds(trials=1) bit for bit")


# ------------------------------ four chips -----------------------------------

def phase_sharded(chips: int):
    from repro.core import scenario1, sweep
    specs = sweep_specs(loads=(4,))
    out = {}
    for d in (chips, 1):
        t0 = time.perf_counter()
        out[d] = sweep(specs, scenario1(), N, trials=SWEEP_TRIALS,
                       chunk=SWEEP_CHUNK, ks=K, seed=0, devices=d)
        info(f"sweep devices={d}: wall {time.perf_counter() - t0:.3f}s "
             f"(compile included)")
    check(f"sweep_devices_{chips}_equals_1",
          all(np.array_equal(out[chips].means[nm], out[1].means[nm])
              and np.array_equal(out[chips].stderr[nm], out[1].stderr[nm])
              for nm in out[1].means),
          f"means and stderrs of {[sp.name for sp in specs]} bit-identical")
    rounds = {}
    for d in (chips, 1):
        t0 = time.perf_counter()
        rounds[d] = run_rounds(devices=d)
        info(f"sweep_rounds devices={d}: wall "
             f"{time.perf_counter() - t0:.3f}s (compile included)")
    check(f"rounds_devices_{chips}_equals_1",
          same_rounds(rounds[chips], rounds[1]),
          "adaptive per-round means, stderrs and wall-clock curves "
          "bit-identical")


# --------------------------------- main --------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the trial-sharded sweeps, "
                         "devices=4 against devices=1")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    stats = CompileStats()

    def phase(name, fn, *a):
        print(f"[phase {name}]", flush=True)
        t0 = time.perf_counter()
        out = fn(*a)
        info(f"phase {name} done in {time.perf_counter() - t0:.1f}s")
        return out

    device = phase("0 device", phase_device, args.chips)
    if args.chips > 1:
        phase(f"sharded x{args.chips}", phase_sharded, args.chips)
    else:
        phase("1 sweep", phase_sweep)
        phase("2 rounds", phase_rounds)
        phase("3 kernel", phase_kernel)
        phase("4 train", phase_train)
        phase("5 live", phase_live)
    print(f"[compile] backend compile {stats.compile_s:.1f}s, persistent "
          f"cache hits {stats.hits} misses {stats.misses}, cache {cache_dir}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
