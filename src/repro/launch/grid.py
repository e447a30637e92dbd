"""Full-grid sweep CLI: stream a (scheme family × load × message budget ×
comm_eps × k) grid through the bucketed Monte-Carlo executors and write the
versioned grid-result artifact (``repro.core.grid.GridResult``).

The grid comes from a ``GridSpec`` — either a JSON document (``--spec``,
the ``GridSpec.to_json`` format) or inline axes:

  python -m repro.launch.grid --n 16 --families cs ss lb pc \\
      --loads 2 4 8 --messages none 2 4 --trials 1000000 \\
      --out out/grid_result.json

  python -m repro.launch.grid --spec grid.json --model ec2 --devices 4

``--devices N`` shards the trial axis over the first N local devices (the
usual forced-host-mesh ``XLA_FLAGS=--xla_force_host_platform_device_count``
applies); ``--window`` sets how many fused dispatches stay in flight
(2 = double buffering; ``--pipeline`` is a compatibility alias).  The
artifact is consumable by ``GridResult.load`` and feeds the racing
planner (``python -m repro.launch.plan`` finds the same winner without
streaming the whole grid).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from ..core.delays import ec2_like, scenario1, scenario2
from ..core.grid import FAMILIES, GridResult, GridSpec, stream_grid
from ..core.montecarlo import cache_stats
from ..compile_cache import enable_compile_cache

MODELS = ("scenario1", "scenario2", "ec2")


def _build_model(name: str, n: int, seed: int):
    if name == "scenario1":
        return scenario1()
    if name == "scenario2":
        return scenario2(n, seed=seed)
    if name == "ec2":
        return ec2_like(n, seed=seed)
    raise SystemExit(f"unknown --model {name!r}; have {MODELS}")


def _axis(vals, cast):
    """Parse an axis list where the token ``none`` means None."""
    return tuple(None if str(v).lower() == "none" else cast(v) for v in vals)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.grid",
        description="Stream a full scheme/load/budget grid and write a "
                    "versioned grid-result artifact.")
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
    ap.add_argument("--ks", nargs="+", default=["none"],
                    help="computation targets (int or 'none' = all k)")
    ap.add_argument("--trials", type=int, default=20000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--model", default="scenario1", choices=list(MODELS))
    ap.add_argument("--devices", type=int, default=None,
                    help="shard trials over the first N local devices")
    ap.add_argument("--window", "--pipeline", dest="window", type=int,
                    default=2,
                    help="streaming window: fused dispatches kept in "
                         "flight (2 = double buffering; --pipeline is an "
                         "alias)")
    ap.add_argument("--k", type=int, default=None,
                    help="computation target for the winner report "
                         "(defaults to each cell's ks, else n)")
    ap.add_argument("--out", default="out/grid_result.json",
                    help="artifact path (directories are created)")
    return ap


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
                      comm_eps=tuple(args.eps), ks=_axis(args.ks, int),
                      trials=args.trials, seed=args.seed, chunk=args.chunk)
    model = _build_model(args.model, gs.n, gs.seed)
    cells = gs.cells(model)
    print(f"grid: {len(cells)} cells (n={gs.n}, trials={gs.trials:,}/cell, "
          f"model={args.model})", flush=True)

    res = stream_grid(cells, devices=args.devices, pipeline=args.window)
    res.meta["model"] = args.model
    res.meta["spec"] = gs.to_json()
    res.meta["window"] = args.window
    res.meta["cache"] = cache_stats()

    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    res.save(args.out)

    m = res.meta
    print(f"done: {m['cells']} cells in {m['seconds']:.2f}s "
          f"({m['cells_per_sec']:.2f} cells/s), "
          f"{m['fused_dispatches']} fused dispatches, "
          f"{m['buckets']} shape bucket(s), window {args.window}")
    try:
        best = res.best_cell(k=args.k)
        tie = f", {len(best['ties'])} tie(s) within 2 sigma" \
            if best["ties"] else ""
        print(f"best: {best['cell']} mean {best['mean']:.6g} "
              f"+- {best['stderr']:.2g}{tie}")
    except ValueError:
        pass        # rounds-only or lb-only grids have no scalar winner
    print(f"artifact: {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
