"""Production training launcher.

Runs straggler-scheduled training of any ``--arch`` (full or ``--smoke``
reduced config) with the paper's CS/SS/RA schedules, round-aware cluster
processes, and optional adaptive row re-assignment. On real hardware the
same entrypoint shards over the production mesh (``--mesh pod|multipod``);
on this CPU container use ``--smoke --mesh local``.

  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
      --smoke --steps 20 --n 4 --r 2 --k 3 --schedule ss \
      --cluster markov --persistence 0.95 --spread 3 --adaptive

Record / replay: ``--log-delays PATH`` writes every round's realized
per-(worker, slot) delays to a versioned trace file
(``repro.core.trace``); ``--cluster trace --trace PATH`` drives a later
run from such a recording (or from delay tables recorded by
``sweep_rounds``) instead of a parametric model.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..core import (AR1Process, AdaptiveScheduler, BimodalStragglerDelays,
                    DelayTrace, FAULT_SCENARIOS, RoundConfig, TraceProcess,
                    ec2_cluster, heterogeneous_scales, load_trace,
                    make_scenario, make_scheduler, save_trace, scenario1)
from ..data import TaskPartition, lm_task_batches
from ..models import num_params
from ..optim import adamw, cosine_schedule
from ..sharding import mesh_context
from ..train import init_train_state, make_straggler_train_step
from ..ckpt import save_checkpoint, load_checkpoint, latest_checkpoint
from ..compile_cache import enable_compile_cache
from .mesh import make_mesh_ctx


def derive_seeds(seed: int) -> dict:
    """Deterministically derive every randomness stream of a run from one
    root ``--seed``: independent keys/ints for parameter init, the data
    pipeline, the per-round delay realizations, and schedule construction
    (RA matrices), via ``fold_in`` on the root key.  Same seed -> same
    run; different seeds decorrelate every stream at once."""
    root = jax.random.PRNGKey(seed)

    def _int(i):
        return int(np.asarray(jax.random.fold_in(root, i))[1])

    return {"init_key": jax.random.fold_in(root, 0),
            "delay_root": jax.random.fold_in(root, 1),
            "data_seed": _int(2),
            "schedule_seed": _int(3),
            "cluster_seed": _int(4)}


def build_cluster(args, seeds):
    """The round delay source: an i.i.d. model, a stateful process, or a
    recorded trace replay.  ``--straggle`` layers i.i.d. bimodal slowdowns
    on the base model in the parametric modes (stateful processes add
    their own regime chain on top); ``--scenario`` overlays a named fault
    scenario (spot preemption, partition, ...) on whatever source was
    built."""
    if args.cluster == "trace":
        if not args.trace:
            raise SystemExit("--cluster trace needs --trace PATH "
                             "(a file written by --log-delays or "
                             "repro.core.save_trace)")
        delay = TraceProcess(load_trace(args.trace),
                             pad_rounds=args.trace_pad)
        if getattr(args, "scenario", "none") != "none":
            raise SystemExit("--scenario cannot overlay a trace replay: "
                             "the recording already realized its faults")
        return delay
    base = (BimodalStragglerDelays(p_straggle=0.3, slow=8.0)
            if args.straggle else scenario1())
    if args.cluster == "iid":
        delay = base
    elif args.cluster == "markov":
        delay = ec2_cluster(args.n, spread=args.spread, p_slow=args.p_slow,
                            persistence=args.persistence, slow=args.slow,
                            base=base, seed=seeds["cluster_seed"])
    else:
        delay = AR1Process(base=base,
                           worker_scale=heterogeneous_scales(
                               args.n, args.spread,
                               seed=seeds["cluster_seed"]),
                           rho=args.persistence, sigma=0.4)
    if getattr(args, "scenario", "none") != "none":
        delay = make_scenario(args.scenario, delay, args.n)
    return delay


@dataclasses.dataclass
class TrainRun:
    """What ``build_run`` sets up for the training loop."""
    spec: RoundConfig           # the round
    part: TaskPartition
    state: object               # TrainState, resumed or fresh
    start: int                  # first step to run
    step_fn: object             # jitted straggler train step
    base_C: np.ndarray          # the round's TO matrix
    sched: AdaptiveScheduler | None


def straggler_rounds(run: TrainRun, delay_root, steps: int, *,
                     reissue: bool = False):
    """The training loop from ``run.start``, one straggler round per SGD
    step: build the round's slot batches from the (adaptively permuted)
    TO matrix, take the step, and feed the round's observed delays back
    to ``run.sched`` (``None`` for a static schedule).  ``reissue`` gives
    the tasks a round left undelivered re-gather priority in the next.
    Yields ``(step, state, metrics)`` after every step."""
    state, sched, cluster = run.state, run.sched, None
    for i in range(run.start, steps):
        C = run.base_C if sched is None else sched.matrix()
        row = None if sched is None else jnp.asarray(sched.row_of_worker())
        toks, labs = lm_task_batches(run.part, C, i)
        state, m, cluster = run.step_fn(state, toks, labs,
                                        jax.random.fold_in(delay_root, i),
                                        cluster, row)
        if sched is not None:
            sched.observe(np.asarray(m["worker_t1"]))
            if reissue:
                sched.set_need(~np.asarray(m["delivered_tasks"]))
        yield i, state, m


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Straggler-scheduled training with record/replay "
                    "delay sources.",
        epilog="Determinism: a single --seed derives every randomness "
               "stream (parameter init, data pipeline, per-round delay "
               "realizations, RA schedule construction) via fold_in, so "
               "one integer pins the whole run; --log-delays / --cluster "
               "trace make the delay stream itself recordable and "
               "replayable.")
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--config", default=None, metavar="PATH",
                    help="load the round configuration from a serialized "
                         "repro.core.RoundConfig JSON document "
                         "(RoundConfig.save / to_json); overrides --n/--r/"
                         "--k/--schedule/--loads/--adaptive/--deadline/"
                         "--deadline-policy/--dead-after")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--r", type=int, default=2)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--schedule", default="ss", choices=("cs", "ss", "ra",
                                                         "block"))
    ap.add_argument("--adaptive", action="store_true",
                    help="re-assign schedule rows each round from feedback")
    ap.add_argument("--loads", default=None,
                    help="comma-separated per-worker loads (ragged rounds), "
                         "e.g. 3,1,2,3 — each <= r; r is then the grid "
                         "width / load cap")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0,
                    help="root seed; deterministically derives the data, "
                         "delay, and schedule/init keys (fold_in streams "
                         "0..4), so one integer reproduces the whole run")
    ap.add_argument("--straggle", action="store_true",
                    help="layer i.i.d. bimodal slowdowns on the base "
                         "delays (parametric cluster modes)")
    ap.add_argument("--cluster", default="iid",
                    choices=("iid", "markov", "ar1", "trace"),
                    help="round-aware delay process for the virtual "
                         "cluster; 'trace' replays a recorded delay trace "
                         "(--trace PATH)")
    ap.add_argument("--trace", default=None,
                    help="delay-trace file (.npz from --log-delays or "
                         "repro.core.save_trace) for --cluster trace")
    ap.add_argument("--trace-pad", default="error",
                    choices=("error", "cycle", "hold"),
                    help="what to do when --steps exceeds the recorded "
                         "rounds: fail, wrap around, or hold the final "
                         "round")
    ap.add_argument("--log-delays", default=None, metavar="PATH",
                    help="record every round's realized per-(worker, "
                         "slot) compute/comm delays and write them to "
                         "PATH as a versioned delay trace (replayable "
                         "via --cluster trace)")
    ap.add_argument("--scenario", default="none",
                    choices=("none",) + FAULT_SCENARIOS,
                    help="overlay a named fault scenario (workers die / "
                         "partition / drop messages) on the parametric "
                         "cluster modes")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-round wall-clock cap (seconds, virtual); "
                         "under faults a round may otherwise never reach "
                         "k results")
    ap.add_argument("--deadline-policy", default="wait",
                    choices=("wait", "close_partial", "reissue"),
                    help="fallback at the deadline: report+flag the miss, "
                         "close with whatever arrived, or close partial "
                         "and re-gather undelivered tasks next round "
                         "(reissue needs --adaptive)")
    ap.add_argument("--dead-after", type=int, default=None,
                    help="adaptive crash detection: presume a worker dead "
                         "(shed its load) after this many consecutive "
                         "rounds with no delivery")
    ap.add_argument("--persistence", type=float, default=0.9,
                    help="straggler persistence (markov) / AR(1) rho")
    ap.add_argument("--spread", type=float, default=2.0,
                    help="worker speed heterogeneity (geometric spread)")
    ap.add_argument("--p-slow", type=float, default=0.2)
    ap.add_argument("--slow", type=float, default=5.0)
    ap.add_argument("--mesh", default="local",
                    choices=("local", "pod", "multipod"))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    return ap


def round_config(args, seeds) -> RoundConfig:
    """The run's one validated round description, from ``--config`` or
    from the round flags (``args`` is updated to match a loaded
    document).  Raises ``ValueError`` on an invalid round."""
    if args.config:
        rc = RoundConfig.load(args.config)
        args.n, args.k, args.schedule = rc.n, rc.k, rc.kind
        args.r = rc.width
        args.adaptive = rc.adaptive
        args.deadline = rc.deadline
        args.deadline_policy = rc.deadline_policy
        args.dead_after = rc.dead_after
        return rc
    loads = (tuple(int(v) for v in args.loads.split(","))
             if args.loads else None)
    return RoundConfig(
        n=args.n, k=args.k, kind=args.schedule,
        r=args.n if args.schedule == "ra" else args.r, loads=loads,
        deadline=args.deadline, deadline_policy=args.deadline_policy,
        adaptive=args.adaptive, dead_after=args.dead_after,
        seed=seeds["schedule_seed"])


def build_run(args, cfg, seeds, rc: RoundConfig) -> TrainRun:
    """The launcher's set-up for model ``cfg`` under round ``rc``: the
    task partition, AdamW on the cosine schedule, the train state (resumed
    from the newest checkpoint under ``--resume``), the delay source, the
    jitted straggler step and, with ``--adaptive``, the scheduler.  Call
    it inside the run's mesh context.  Raises ``ValueError`` on a round
    the train loop does not run: its feedback is the uncensored per-worker
    compute mean, and its slot batches cannot follow a re-balance."""
    if rc.censored_feedback or rc.rebalance:
        raise ValueError("the train loop runs neither censored_feedback nor "
                         "rebalance; drop them from the round config")
    delay = build_cluster(args, seeds)
    part = TaskPartition(n=args.n, global_batch=args.batch,
                         seq_len=args.seq, vocab=cfg.vocab_size,
                         source="bigram", seed=seeds["data_seed"])
    opt = adamw(cosine_schedule(args.lr, args.steps, warmup=5))
    state = init_train_state(seeds["init_key"], cfg, opt)
    start = 0
    if args.resume and args.ckpt_dir:
        path = latest_checkpoint(args.ckpt_dir, args.arch)
        if path:
            state = load_checkpoint(path, state)
            start = int(state.step)
            print(f"resumed from {path} at step {start}")
    if isinstance(delay, TraceProcess) and start:
        # resumed runs keep their remaining steps aligned with the
        # trace rounds those steps originally consumed
        delay = dataclasses.replace(delay, start_round=start)
    if hasattr(delay, "check_rounds"):
        # fail fast (with the remedy) instead of r rounds into the run
        delay.check_rounds(args.steps - start)
    step_fn = jax.jit(make_straggler_train_step(cfg, opt, rc, delay))
    return TrainRun(rc, part, state, start, step_fn, rc.to_matrix(),
                    make_scheduler(rc))


def main(argv=None):
    enable_compile_cache()
    args = build_parser().parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        if cfg.arch_type == "hybrid":
            cfg = dataclasses.replace(cfg, ssm_period=2, ssm_attn_offset=1)
    if args.mesh == "local":
        ctx = None
    else:
        ctx = make_mesh_ctx(multi_pod=args.mesh == "multipod")
    if cfg.frontend_seq or cfg.encoder_layers:
        raise SystemExit("use text archs for this launcher; whisper/llava "
                         "training is exercised via tests + dryrun")

    if args.log_delays:
        # fail fast on an unwritable destination instead of after the
        # whole run has been spent recording
        out_dir = os.path.dirname(os.path.abspath(args.log_delays))
        os.makedirs(out_dir, exist_ok=True)
        if not os.access(out_dir, os.W_OK):
            raise SystemExit(f"--log-delays: cannot write to {out_dir}")
    seeds = derive_seeds(args.seed)
    # ONE validation path: every round field funnels through RoundConfig
    # (k/r ranges, ragged coverage, deadline/policy pairing, the adaptive-
    # family cross-field rules) whether it came from flags or --config.
    try:
        rc = round_config(args, seeds)
    except ValueError as e:
        raise SystemExit(str(e))
    loads = rc.loads

    with mesh_context(ctx):
        try:
            run = build_run(args, cfg, seeds, rc)
        except ValueError as e:
            raise SystemExit(str(e))
        spec, start, state = run.spec, run.start, run.state
        print(f"{cfg.name}: {num_params(state.params):,} params | "
              f"round n={spec.n} r={spec.width} k={spec.k} {args.schedule}"
              f"{'+adaptive' if args.adaptive else ''}"
              f"{' loads=' + ','.join(map(str, loads)) if loads else ''} | "
              f"cluster {args.cluster}"
              f"{' +' + args.scenario if args.scenario != 'none' else ''}"
              f"{f' deadline={args.deadline:g}/{args.deadline_policy}' if args.deadline is not None else ''}")
        vclock = 0.0
        missed = 0
        realized_sum = 0.0
        logged_t1, logged_t2 = [], []
        t0 = time.time()
        for i, state, m in straggler_rounds(
                run, seeds["delay_root"], args.steps,
                reissue=args.deadline_policy == "reissue"):
            if args.log_delays:
                logged_t1.append(np.asarray(m["slot_t1"]))
                logged_t2.append(np.asarray(m["slot_t2"]))
            vclock += float(m["completion_time"])
            missed += int(bool(m["deadline_missed"]))
            realized_sum += float(m["realized_k"])
            if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
                print(f"step {i:5d}  loss {float(m['loss']):.4f}  "
                      f"gnorm {float(m['grad_norm']):.3f}  "
                      f"vclock {vclock * 1e3:.2f} ms")
        rounds_run = args.steps - start
        print(f"done: {rounds_run} rounds in "
              f"{time.time() - t0:.1f}s wall, {vclock * 1e3:.2f} ms virtual")
        if args.deadline is not None and rounds_run:
            print(f"deadline {args.deadline:g}s/{args.deadline_policy}: "
                  f"{missed}/{rounds_run} rounds missed, mean realized k "
                  f"{realized_sum / rounds_run:.2f}/{spec.k}")
        if args.log_delays and logged_t1:
            trace = DelayTrace(
                np.stack(logged_t1), np.stack(logged_t2),
                meta={"source": "launch.train", "arch": args.arch,
                      "schedule": args.schedule, "cluster": args.cluster,
                      "n": args.n, "r": spec.width, "k": args.k,
                      "seed": args.seed, "start_step": start,
                      "adaptive": bool(args.adaptive)})
            p = save_trace(args.log_delays, trace)
            print(f"logged {trace.rounds} rounds of delays -> {p} "
                  f"(replay with --cluster trace --trace {p})")
        if args.ckpt_dir:
            p = save_checkpoint(f"{args.ckpt_dir}/{args.arch}", state,
                                step=args.steps)
            print("saved", p)


if __name__ == "__main__":
    main()
