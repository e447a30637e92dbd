"""The chip benchmark: one cell of BENCHMARK.json, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (``configs[].file``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The mix names the driver that runs it
(``drivers/<driver>.py``); each per-layer metric is read by
``metrics/<name>.py``.  All three are found by name in the directories of
``paths``, so a later cell or metric is added as new files only.

The run: set-up (imports, device check, the driver's build and warm-up,
all timed as ``setup_s``), a window of ``--seconds`` of the driver's calls
into the program, then the driver's comparison of what the window produced
with a plain reference.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: every number
compared, beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
DOC = ROOT / "BENCHMARK.json"
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, an unknown device, a
    malformed cell)."""


@dataclasses.dataclass
class Run:
    """What a driver and the metric readers see of one run."""
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    attempted: int = 0
    failed: int = 0
    work: float = 0.0               # the driver's unit: evaluations, ...
    setup_s: float = 0.0
    window_s: float = 0.0
    compiles_in_window: int = 0
    trace_summary: dict | None = None
    extra: dict = dataclasses.field(default_factory=dict)  # per-window
    # records a driver leaves for the metric readers (plan: decisions)


def load_plugin(paths, root: Path, kind: str, name: str):
    """The module ``<p>/<kind>/<name>.py`` for the first ``p`` of
    ``paths`` that has it."""
    for p in paths:
        f = root / p / kind / f"{name}.py"
        if f.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), f)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise BenchError(f"no {kind}/{name}.py under {paths}")


def find_data(paths, root: Path, kind: str, name: str) -> dict:
    for p in paths:
        f = root / p / kind / f"{name}.json"
        if f.is_file():
            return json.loads(f.read_text())
    raise BenchError(f"no {kind}/{name}.json under {paths}")


def cell_spec(doc: dict, root: Path, workload: str):
    """(workload entry, configuration, traffic, e2e metrics, per-layer
    metrics) of one cell."""
    cells = {w["name"]: w for w in doc["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in doc["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = find_data(doc["paths"], root, "traffic", w["traffic"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in doc["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in doc["per_layer"]
             if applies(m) and m["moves"] in e2e_names]
    return w, config, traffic, e2e, layer


def device_info(chips: int) -> dict:
    """The attached devices as JAX reports them; a TPU with at least
    ``chips`` chips, or ``BenchError``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r} "
                         f"devices only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def load_peaks(root: Path, kind: str) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"device {kind!r} is not in bench/peaks.json; "
                         f"have {sorted(table['devices'])}")
    return table["devices"][kind]


def memory_peak(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def window(run: Run, cell, counter) -> None:
    """Call the driver for ``run.seconds``; every call is one attempt."""
    import jax
    c0 = counter.compiles
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            i = run.attempted
            run.attempted += 1
            try:
                run.work += cell.call(i)
            except Exception:                    # noqa: BLE001 - counted
                run.failed += 1
                traceback.print_exc()
            if time.perf_counter() - t0 >= run.seconds:
                break
    run.window_s = time.perf_counter() - t0
    run.compiles_in_window = counter.compiles - c0


def traced_window(run: Run, cell, counter) -> None:
    import jax
    from bench import trace_reduce
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        window(run, cell, counter)
    finally:
        jax.profiler.stop_trace()
    run.trace_summary = trace_reduce.reduce_dir(
        TRACE_DIR, chips=run.workload["chips"])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, doc_path: Path = DOC) -> int:
    args = parse(argv)
    doc = json.loads(Path(doc_path).read_text())
    w, config, traffic, e2e, layer = cell_spec(doc, ROOT, args.workload)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from repro.compile_cache import enable_compile_cache
    import jax
    from bench.counters import CompileCounter
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()

    device = device_info(w["chips"])
    run = Run(workload=w, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              peaks=load_peaks(ROOT, device["kind"]))
    driver = load_plugin(doc["paths"], ROOT, "drivers", traffic["driver"])
    cell = driver.setup(run)
    run.setup_s = time.perf_counter() - T_START

    (traced_window if run.trace else window)(run, cell, counter)
    device["memory_peak_bytes"] = memory_peak(w["chips"])
    metrics = {}
    if run.trace:
        ts = run.trace_summary
        device["busy_s"] = ts["busy_s"]
        device["window_s"] = ts["window_s"]
        for m in layer:
            v = load_plugin(doc["paths"], ROOT, "metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(cell.end_to_end(run), setup_s=run.setup_s)
        for m in e2e:
            if m["name"] not in values:
                raise BenchError(f"driver {traffic['driver']} gave no "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    t_check = time.perf_counter()
    checks = cell.check(run)
    print(f"bench: setup {run.setup_s:.3f}s, window {run.window_s:.3f}s, "
          f"{run.attempted} calls, {run.compiles_in_window} compiles in the "
          f"window, check {time.perf_counter() - t_check:.3f}s",
          file=sys.stderr, flush=True)
    correct = (run.failed == 0 and run.attempted > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace:
        out["breakdown"] = run.trace_summary["breakdown"]
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(3)
