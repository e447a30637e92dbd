"""Benchmark harness — one entry per paper table/figure (deliverable (d)).

Prints ``name,us_per_call,derived`` CSV rows:
  fig3   delay-model calibration (comm >> comp)
  fig4   avg completion vs r, truncated-Gaussian scenarios 1 & 2 (n=16)
  fig5   avg completion vs r, EC2-calibrated model (n=15)
  fig6   avg completion vs n (r=n)
  fig7   avg completion vs k (n=10, r=n)
  fig8   rounds-axis wall-clock: persistence x heterogeneity grid, static
         CS/SS vs feedback-adaptive row assignment vs oracle LB
  fig9   intra-round message budget m in {1, 2, r} for CS/SS/PCMM
         (paper Sec. V-C; exits non-zero if multi-message stops beating
         single-message), plus the Ozfatura-style per-message overhead
         sweep reporting the optimal budget m*(eps)
  fig10  adaptive load re-balancing (ragged per-worker loads, Egger-style)
         vs static CS/SS and permutation-only adaptation on the
         heterogeneous persistent cluster (exits non-zero unless
         re-balancing beats all three)
  fig11  trace record -> replay -> calibrate loop: records the
         heterogeneous cell's delays, round-trips the versioned trace
         file, replays it (exits non-zero unless bit-exact), and checks
         the calibrated synthetic twin keeps the adaptive-vs-static
         margin sign
  fig12  fault injection and graceful degradation: the failure-scenario
         zoo under round deadlines (exits non-zero unless adaptive +
         close_partial beats static under preemption, every scenario
         stays finite, and the fault-bearing trace replays bit-exactly)
  fig13  live execution layer vs the simulator: an async in-process
         master-worker run must match ``sweep_rounds`` bit-exactly
         (shared-seed tables + the engine's fused scorer), its recorded
         trace must replay bit-exactly, its mean must sit inside the MC
         prediction's sampling tolerance, and deadline degradation
         accounting must match the engine's streams (non-zero exit on
         any violation)
  mc_engine  fused sweep-engine throughput vs the seed per-scheme path
  grid   streaming grid-sweep engine (repro.core.grid) vs the naive
         loop-of-sweeps baseline: cells/sec, one-compile-per-bucket, and
         CRN bit-exactness (non-zero exit on a retrace or stats mismatch);
         also writes the GRID_result.json artifact into --out
  planner  racing planner vs the exhaustive grid on the same 64 cells:
         must name the same argmin operating point (non-zero exit on
         disagreement) while spending a fraction of the trial-evaluations
         (the ``saved`` ratio, gated via ``planner_trials_saved_min``)
  table1 end-to-end DGD iteration per scheme incl. real PC/PCMM decode
  roofline  per-(mesh, arch, shape) terms from saved dry-run artifacts

Each job also writes a machine-readable ``BENCH_<name>.json`` (the CSV rows
with parsed derived metrics) into ``--out`` for CI artifact upload and the
``benchmarks.regression_gate`` check.

Every drained row is screened for NaN/inf metric values: a non-finite
number in a derived field aborts the harness with a non-zero exit and an
explicit message, so a silently-poisoned benchmark can never look green.

Use --quick for CI-speed runs (fewer MC trials).
"""
import argparse
import json
import math
import os
import time


def _check_finite(name: str, rows: list) -> None:
    """Fail loudly (non-zero exit) when a benchmark emits NaN/inf metrics."""
    bad = [(row["name"], key, val)
           for row in rows for key, val in row.get("derived", {}).items()
           if isinstance(val, float) and not math.isfinite(val)]
    if bad:
        lines = "; ".join(f"{r}:{k}={v}" for r, k, v in bad)
        raise SystemExit(
            f"benchmarks.run: benchmark {name!r} emitted non-finite "
            f"metric(s): {lines} — refusing to report poisoned results")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer Monte-Carlo trials")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig4,fig7")
    ap.add_argument("--out", default="bench_out",
                    help="directory for BENCH_<name>.json artifacts "
                         "(created if needed; '' disables JSON output)")
    args = ap.parse_args(argv)
    trials = 4000 if args.quick else 20000
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (common, fig3_delays, fig4_vs_load, fig5_ec2,
                   fig6_vs_workers, fig7_vs_target, fig8_convergence,
                   fig9_multimessage, fig10_load_rebalance,
                   fig11_trace_replay, fig12_faults, fig13_live,
                   grid_stream, mc_engine, planner, table1_e2e,
                   roofline_report)

    jobs = {
        "fig3": lambda: fig3_delays.run(trials),
        "fig4": lambda: fig4_vs_load.run(trials),
        "fig5": lambda: fig5_ec2.run(trials),
        "fig6": lambda: fig6_vs_workers.run(trials),
        "fig7": lambda: fig7_vs_target.run(trials),
        "fig8": lambda: fig8_convergence.run(trials),
        "fig9": lambda: fig9_multimessage.run(trials),
        "fig10": lambda: fig10_load_rebalance.run(trials),
        "fig11": lambda: fig11_trace_replay.run(trials,
                                                out=args.out or "bench_out"),
        "fig12": lambda: fig12_faults.run(trials,
                                          out=args.out or "bench_out"),
        "fig13": lambda: fig13_live.run(trials),
        "mc_engine": lambda: mc_engine.run(trials),
        "grid": lambda: grid_stream.run(trials,
                                        out=args.out or "bench_out"),
        "planner": lambda: planner.run(trials),
        "table1": table1_e2e.run,
        "roofline": roofline_report.run,
    }
    if only:
        unknown = sorted(only - set(jobs))
        if unknown:
            raise SystemExit(
                f"benchmarks.run: unknown --only name(s) {unknown}; "
                f"valid names: {sorted(jobs)}")

    print("name,us_per_call,derived")
    for name, job in jobs.items():
        if only and name not in only:
            continue
        common.drain_rows()            # drop strays from earlier jobs
        try:
            job()
        finally:
            # write the artifact even when a guard fails (fig8/fig9 exit
            # non-zero): the per-scheme rows are the diagnosis.
            rows = common.drain_rows()
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                path = os.path.join(args.out, f"BENCH_{name}.json")
                with open(path, "w") as f:
                    json.dump({"bench": name, "quick": bool(args.quick),
                               "trials": trials, "unix_time": time.time(),
                               "rows": rows}, f, indent=2)
                    f.write("\n")
        _check_finite(name, rows)


if __name__ == "__main__":
    main()
