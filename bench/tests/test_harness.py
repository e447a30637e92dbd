"""The harness: it finds a cell's files by name, refuses to run without a
TPU, and knows no device outside its peaks table."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = ROOT / "bench" / "tests" / "fixture" / "BENCHMARK.json"


def _bench(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_to_run_without_a_tpu():
    p = _bench(["--workload", "mc_sweep.s1-n16", "--seed", "1",
                "--seconds", "1"], ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


def test_needs_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = _bench(["--workload", "mc_sweep.s1-n16", "--seed", "1",
                "--seconds", "1"], tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_unknown_device_is_an_error():
    assert bench_run.load_peaks(ROOT, "TPU v5 lite")["bf16_flops_per_s"] > 0
    with pytest.raises(bench_run.BenchError, match="peaks.json"):
        bench_run.load_peaks(ROOT, "TPU v99")


def test_every_cell_of_the_benchmark_resolves():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in doc["workloads"]:
        _, config, traffic, e2e, layer = bench_run.cell_spec(doc, ROOT,
                                                             w["name"])
        bench_run.load_plugin(doc["paths"], ROOT, "drivers",
                              traffic["driver"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer
        for m in layer:
            assert hasattr(bench_run.load_plugin(doc["paths"], ROOT,
                                                 "metrics", m["name"]),
                           "read")


def test_a_cell_added_as_new_files_only():
    """The fixture's configuration, traffic and metric live only in
    ``bench/tests/fixture/``; the harness finds them by name."""
    doc = json.loads(FIXTURE.read_text())
    w, config, traffic, e2e, layer = bench_run.cell_spec(doc, ROOT,
                                                         "fixture.tiny")
    assert config["name"] == "tiny" and traffic["trials"] == 262144
    assert [m["name"] for m in layer] == ["fixture_calls",
                                          "compiles_in_window.mc"]
    run = bench_run.Run(workload=w, config=config, traffic=traffic, seed=1,
                        seconds=1, trace=True, peaks={}, attempted=3)
    reader = bench_run.load_plugin(doc["paths"], ROOT, "metrics",
                                   "fixture_calls")
    assert reader.read(run) == 3.0
    with pytest.raises(bench_run.BenchError):
        bench_run.load_plugin(doc["paths"], ROOT, "metrics", "no_such")
