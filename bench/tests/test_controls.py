"""The controls and planted faults that set the upper readings of the
comparisons deciding ``correct``, at a CPU size.

- The control puts the plain reference in the program's place, computed
  in bfloat16 (the configurations state float32): the comparison has to
  fail it.
- The faults break the timed path underneath a whole run (device check
  skipped): the run has to come out not correct.

At a cell's own size, on the chip, the same readings come from

    python3 bench/tests/test_controls.py --workload <cell> --seeds <s> ...

which prints one JSON line per seed with the control's and each fault's
numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run as bench_run  # noqa: E402
from bench.seeds import derive  # noqa: E402

FIXTURE = ROOT / "bench" / "tests" / "fixture" / "BENCHMARK.json"


def _block(trials: int, most: int = 16384) -> int:
    """The largest block of at most ``most`` trials that divides
    ``trials``."""
    return next(b for b in range(min(trials, most), 0, -1) if trials % b == 0)


def control_readings(traffic: dict, config: dict, seed: int) -> dict:
    """The numbers the cell's check compares, with the reference computed
    in bfloat16 in the program's place."""
    import jax.numpy as jnp
    from bench.refs.round_mc import round_means
    n, chk = int(config["n"]), traffic["check"]
    delays = config["delays"]
    if traffic["driver"] == "sweep":
        from bench.drivers.sweep import compare
        t = traffic["trials"]
        got = round_means(traffic["schemes"], delays, n, traffic["k"], t,
                          derive(seed, "control"), block=_block(t),
                          dtype=jnp.bfloat16)
        ref = round_means(traffic["schemes"], delays, n, traffic["k"],
                          chk["ref_trials"], derive(seed, "reference", 0))
        z, dev = compare(got, t, ref, chk["ref_trials"])
        return {"z_max": z, "se_ratio_dev": dev}
    if traffic["driver"] == "plan":
        from bench.drivers.plan import check_decisions, raceable_points
        g = traffic["grid"]
        decisions = {}
        for gs in traffic["grid_seeds"]:
            points = raceable_points(g, n, gs)
            got = round_means(points, delays, n, traffic["k"], g["trials"],
                              derive(seed, "control", gs),
                              block=_block(g["trials"]), dtype=jnp.bfloat16)
            w = min(got, key=lambda p: got[p][0])
            decisions[gs] = {"winner": w, "mean": got[w][0], "se": got[w][1]}
        out = check_decisions(decisions, g, n, traffic["k"], delays, chk,
                              derive(seed, "reference"))
        return {name: c["value"] for name, c in out.items()}
    raise ValueError(f"no control for driver {traffic['driver']!r}")


# ------------------------------ planted faults ------------------------------

def _half_trials(sweep):
    """Half of the trials left out, the mean taken over the rest."""
    def broken(specs, model, n, *, trials, chunk, **kw):
        half = trials // 2
        return sweep(specs, model, n, trials=half, chunk=min(chunk, half),
                     **kw)
    return broken


def _wrong_order_stat(sweep):
    """An answer altered where it is produced: the (k-1)-th task closes
    the round."""
    import dataclasses

    def broken(specs, model, n, *, ks, **kw):
        res = sweep(specs, model, n, ks=ks - 1, **kw)
        return dataclasses.replace(res, ks=ks)
    return broken


def _plan_mean_altered(plan):
    """An answer altered where it is produced: the predicted mean of the
    winner read 0.5% high."""
    import dataclasses

    def broken(*a, **kw):
        res = plan(*a, **kw)
        return dataclasses.replace(res,
                                   predicted_mean=res.predicted_mean * 1.005)
    return broken


def _plan_winner_altered(plan):
    """An answer altered where it is produced: the raced point with the
    highest mean is returned as the winner, with its own mean."""
    import dataclasses

    def broken(*a, **kw):
        res = plan(*a, **kw)
        raced = {p: rec for p, rec in res.points.items() if "mean" in rec}
        worst = max(raced, key=lambda p: raced[p]["mean"])
        return dataclasses.replace(res, winner=worst,
                                   predicted_mean=raced[worst]["mean"],
                                   predicted_stderr=raced[worst]["stderr"])
    return broken


FAULTS = {"sweep": {"half_trials": ("sweep", _half_trials),
                    "wrong_order_stat": ("sweep", _wrong_order_stat)},
          "plan": {"mean_altered": ("plan", _plan_mean_altered),
                   "winner_altered": ("plan", _plan_winner_altered)}}


def run_cell(workload: str, seed: int, seconds: float, doc: Path,
             fault=None) -> dict:
    """One whole run of ``workload`` without the device check, with the
    program's entry point broken by ``fault`` (``(name, wrapper)``);
    returns the run's result line."""
    import repro.core as core
    saved = {}
    if fault is not None:
        name, wrap = fault
        saved[name] = getattr(core, name)
        setattr(core, name, wrap(saved[name]))
    devinfo, peaks = bench_run.device_info, bench_run.load_peaks
    kind = None
    try:
        import jax
        if jax.devices()[0].platform != "tpu":
            bench_run.device_info = lambda chips: {
                "platform": "cpu", "kind": "cpu", "count": 1}
            bench_run.load_peaks = lambda root, k: {"bf16_flops_per_s": 197e12}
            kind = "cpu"
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            bench_run.main(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds)], doc_path=doc)
        return json.loads(out.getvalue().strip().splitlines()[-1])
    finally:
        for name, fn in saved.items():
            setattr(core, name, fn)
        if kind:
            bench_run.device_info, bench_run.load_peaks = devinfo, peaks


def _cell(workload: str, doc: Path = FIXTURE):
    d = json.loads(doc.read_text())
    w, config, traffic, _, _ = bench_run.cell_spec(d, ROOT, workload)
    return config, traffic


# ---------------------------------- tests -----------------------------------

@pytest.mark.parametrize("workload", ["fixture.tiny", "fixture.plan"])
def test_control_fails(workload):
    config, traffic = _cell(workload)
    got = control_readings(traffic, config, seed=5)
    lim = traffic["check"]["limits"]
    assert any(got[k] > lim[k] for k in lim), got


@pytest.mark.parametrize("workload,fault", [
    ("fixture.tiny", "half_trials"), ("fixture.tiny", "wrong_order_stat"),
    ("fixture.plan", "mean_altered"), ("fixture.plan", "winner_altered")])
def test_fault_is_not_correct(workload, fault):
    _, traffic = _cell(workload)
    out = run_cell(workload, 7, 1.0, FIXTURE,
                   FAULTS[traffic["driver"]][fault])
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", ["fixture.tiny", "fixture.plan"])
def test_sound_run_is_correct(workload):
    out = run_cell(workload, 2 ** 31 + 9, 1.0, FIXTURE)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


# ------------------------------- at cell size -------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    config, traffic = _cell(args.workload, bench_run.DOC)
    for s in args.seeds:
        row = {"workload": args.workload, "seed": s,
               "control": control_readings(traffic, config, s)}
        for name, fault in FAULTS[traffic["driver"]].items():
            out = run_cell(args.workload, s, args.seconds, bench_run.DOC,
                           fault)
            row[name] = {k: c["value"] for k, c in out["checks"].items()}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
