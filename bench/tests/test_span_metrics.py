"""The readers of the program's spans and counters: per-call numbers from
a synthetic window of recorded spans, and None where the calls the spans
show are not the calls the window attempted, or the program has none."""
import json
import sys
from pathlib import Path

import pytest

from bench import run as bench_run
from repro import obs

ROOT = Path(__file__).resolve().parents[2]
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
MS = 1_000_000                          # ns


def _read(name, run):
    return bench_run.load_plugin(DOC["paths"], ROOT, "metrics",
                                 name).read(run)


def _run(attempted):
    return bench_run.Run(workload={}, config={}, traffic={}, seed=1,
                         seconds=1, trace=True, peaks={},
                         attempted=attempted)


def _decision(base, first):
    """One ``plan.decide`` of 1000 ms starting at ``first`` ms, ids from
    ``base``: prune 100, two rungs (extend 300 and 200 with a 30 ms
    dispatch inside, race 150 and 50), select 20, lb sweep 100."""
    spans = []

    def sp(i, name, parent, a, b):
        spans.append(obs.Span(base + i, name,
                              None if parent is None else base + parent,
                              base, (first + a) * MS, (first + b) * MS, {}))
    sp(0, "plan.decide", None, 0, 1000)
    sp(1, "plan.prune", 0, 10, 110)
    sp(2, "plan.rung", 0, 110, 570)
    sp(3, "engine.extend", 2, 110, 410)
    sp(4, "engine.dispatch", 3, 110, 140)
    sp(5, "plan.race", 2, 410, 560)
    sp(6, "plan.rung", 0, 570, 830)
    sp(7, "engine.extend", 6, 570, 770)
    sp(8, "plan.race", 6, 770, 820)
    sp(9, "plan.select", 0, 830, 850)
    sp(10, "plan.lb_sweep", 0, 850, 950)
    sp(11, "engine.sweep", 10, 851, 949)
    return spans


def _sweep_call(base, first):
    """One ``engine.sweep`` of 100 ms: dispatch 12 (with a 2 ms child),
    wait 80, combine 6."""
    spans = []

    def sp(i, name, parent, a, b):
        spans.append(obs.Span(base + i, name,
                              None if parent is None else base + parent,
                              base, (first + a) * MS, (first + b) * MS, {}))
    sp(0, "engine.sweep", None, 0, 100)
    sp(1, "engine.dispatch", 0, 0, 12)
    sp(2, "test.inside_dispatch", 1, 4, 6)
    sp(3, "engine.wait", 0, 12, 92)
    sp(4, "engine.combine", 0, 92, 98)
    return spans


@pytest.fixture
def recorded(monkeypatch):
    def use(spans, counters):
        monkeypatch.setattr(obs, "spans", lambda: list(spans))
        monkeypatch.setattr(obs, "counters", lambda: dict(counters))
    return use


def test_planner_readers(recorded):
    recorded(_decision(100, 0) + _decision(200, 2000),
             {"engine.fetched_bytes": 3_000_000})
    run = _run(2)
    assert _read("plan_prune_ms", run) == pytest.approx(100.0)
    assert _read("plan_race_ms", run) == pytest.approx(200.0)
    assert _read("plan_extend_ms", run) == pytest.approx(500.0)
    assert _read("plan_lb_ms", run) == pytest.approx(100.0)
    assert _read("plan_fetched_mb", run) == pytest.approx(1.5)


def test_sweep_readers(recorded):
    recorded(_sweep_call(10, 0) + _sweep_call(20, 500)
             + _sweep_call(30, 900), {})
    run = _run(3)
    assert _read("mc_dispatch_ms", run) == pytest.approx(10.0)
    assert _read("mc_combine_ms", run) == pytest.approx(6.0)


@pytest.mark.parametrize("name,spans", [
    ("plan_prune_ms", _decision(100, 0)),
    ("plan_race_ms", _decision(100, 0)),
    ("plan_extend_ms", _decision(100, 0)),
    ("plan_lb_ms", _decision(100, 0)),
    ("plan_fetched_mb", _decision(100, 0)),
    ("mc_dispatch_ms", _sweep_call(10, 0)),
    ("mc_combine_ms", _sweep_call(10, 0)),
])
def test_none_unless_every_attempt_has_its_root(recorded, name, spans):
    recorded(spans, {"engine.fetched_bytes": 10})
    assert _read(name, _run(1)) is not None
    assert _read(name, _run(2)) is None
    recorded([], {})
    assert _read(name, _run(0)) is None


def test_none_for_a_program_without_spans(recorded, monkeypatch):
    recorded(_decision(100, 0), {"engine.fetched_bytes": 10})
    assert _read("plan_prune_ms", _run(1)) is not None
    # a program without repro.obs: the import fails
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in ("plan_prune_ms", "plan_fetched_mb"):
        assert _read(name, _run(1)) is None


def test_a_missing_span_reads_none(recorded):
    spans = [s for s in _decision(100, 0) if s.name != "plan.prune"]
    recorded(spans, {})
    assert _read("plan_prune_ms", _run(1)) is None
    assert _read("plan_fetched_mb", _run(1)) is None
