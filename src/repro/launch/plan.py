"""Racing-planner CLI: find a grid's argmin operating point by
successive-halving with CRN paired elimination (``repro.core.planner``)
instead of streaming the exhaustive grid, and write the versioned
plan-result artifact.

The search space is the same ``GridSpec`` the grid CLI consumes — a JSON
document (``--spec``) or inline axes:

  python -m repro.launch.plan --n 16 --families cs ss ra pc \\
      --loads 2 4 8 16 --messages none 2 --trials 100000 --k 16 \\
      --out out/plan_result.json --emit-config out/round_config.json

``--emit-config`` additionally writes the winning ``RoundConfig`` JSON
when the winner is a TO-matrix family (cs/ss/ra) — feed it straight to
``python -m repro.launch.train --config`` or the live master.  ``--trials``
is the final-rung count, so the reported argmin carries the same
confidence as the exhaustive grid at that budget; the planner typically
spends >= 5x fewer trial-evaluations getting there.

``--profile DIR`` traces the decision with the JAX profiler into ``DIR``
(open it in TensorBoard or Perfetto) and prints, for each of the
program's spans (``repro.obs``: ``plan.prune``, ``plan.race``,
``engine.extend``, ...), how often it ran and its total and self
seconds, then the program's counters.  A first decision in a process
includes its compiles.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import jax

from ..core.grid import FAMILIES, GridSpec
from ..core.planner import plan
from .grid import MODELS, _axis, _build_model
from ..compile_cache import enable_compile_cache
from .. import obs


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.plan",
        description="Race a scheme/load/budget grid to its argmin "
                    "operating point and write a versioned plan-result "
                    "artifact.")
    ap.add_argument("--spec", default=None,
                    help="GridSpec JSON file (overrides the inline axes)")
    ap.add_argument("--n", type=int, default=16, help="cluster size")
    ap.add_argument("--families", nargs="+", default=["cs", "ss", "lb", "pc"],
                    choices=list(FAMILIES), help="scheme families")
    ap.add_argument("--loads", nargs="+", type=int, default=[2],
                    help="computation loads r")
    ap.add_argument("--messages", nargs="+", default=["none"],
                    help="message budgets (int or 'none' = per-task)")
    ap.add_argument("--eps", nargs="+", type=float, default=[0.0],
                    help="per-message comm overheads")
    ap.add_argument("--trials", type=int, default=20000,
                    help="final-rung (= exhaustive-equivalent) trials")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--model", default="scenario1", choices=list(MODELS))
    ap.add_argument("--k", type=int, default=None,
                    help="computation target (default: n)")
    ap.add_argument("--base-trials", type=int, default=None,
                    help="first-rung trials (default trials/eta^3, >= 256)")
    ap.add_argument("--eta", type=int, default=4, help="rung growth factor")
    ap.add_argument("--z", type=float, default=3.0,
                    help="elimination threshold in paired-gap sigmas")
    ap.add_argument("--no-theory-prune", action="store_true",
                    help="skip the closed-form dominance pruning stage")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard trials over the first N local devices")
    ap.add_argument("--emit-config", default=None,
                    help="also write the winning RoundConfig JSON here "
                         "(TO-matrix winners only)")
    ap.add_argument("--out", default="out/plan_result.json",
                    help="artifact path (directories are created)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="trace the decision into DIR with the JAX "
                         "profiler and print each program span's count, "
                         "total and self seconds")
    return ap


def print_spans() -> None:
    """One line per recorded span name (count, total and self seconds,
    largest total first), then the counters."""
    rows = sorted(obs.summary().items(), key=lambda kv: -kv[1][1])
    print(f"{'span':<16} {'count':>6} {'total s':>10} {'self s':>10}")
    for name, (cnt, total, own) in rows:
        print(f"{name:<16} {cnt:>6} {total * 1e-9:>10.4f} "
              f"{own * 1e-9:>10.4f}")
    for name, v in sorted(obs.counters().items()):
        print(f"counter {name} {v}")


def main(argv=None) -> int:
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    if args.spec is not None:
        with open(args.spec) as fh:
            gs = GridSpec.from_json(json.load(fh))
    else:
        gs = GridSpec(n=args.n, families=tuple(args.families),
                      loads=tuple(args.loads),
                      messages=_axis(args.messages, int),
                      comm_eps=tuple(args.eps), ks=(None,),
                      trials=args.trials, seed=args.seed, chunk=args.chunk)
    model = _build_model(args.model, gs.n, gs.seed)
    print(f"plan: racing grid n={gs.n} "
          f"(final rung {gs.trials:,} trials/point, model={args.model})",
          flush=True)

    obs.reset()
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        res = plan(gs, model, k=args.k, base_trials=args.base_trials,
                   eta=args.eta, z=args.z,
                   theory_prune=not args.no_theory_prune,
                   devices=args.devices)
    res.meta["model"] = args.model
    res.meta["spec"] = gs.to_json()

    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    res.save(args.out)

    m = res.meta
    print(f"done: {m['raced_points']} raced / {m['theory_pruned']} pruned "
          f"/ {m['excluded']} excluded of {m['exhaustive_cells']} cells "
          f"in {m['seconds']:.2f}s")
    print(f"winner: {res.winner} mean {res.predicted_mean:.6g} "
          f"+- {res.predicted_stderr:.2g}")
    if res.lb_gap is not None:
        print(f"vs oracle LB: {res.lb_mean:.6g} (+{100 * res.lb_gap:.1f}%)")
    if m["ties"]:
        print(f"ties within {m['z']} sigma: {', '.join(m['ties'])}")
    print(f"trials: {res.trials_spent:,} spent vs "
          f"{res.exhaustive_trials:,} exhaustive ({res.savings:.1f}x saved)")
    if res.config is not None:
        if args.emit_config:
            cfg_dir = os.path.dirname(args.emit_config)
            if cfg_dir:
                os.makedirs(cfg_dir, exist_ok=True)
            res.config.save(args.emit_config)
            print(f"round config: {args.emit_config}")
    elif res.config_note:
        print(f"round config: none ({res.config_note})")
        if args.emit_config:
            print(f"(--emit-config {args.emit_config} skipped)")
    print(f"artifact: {args.out}")
    if args.profile:
        print(f"trace: {args.profile}")
        print_spans()
    return 0


if __name__ == "__main__":
    sys.exit(main())
