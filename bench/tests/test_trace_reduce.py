"""``bench.trace_reduce`` on a small trace recorded on a TPU v5e
(``data/tpu_small.xplane.pb``: three calls of a jitted 1024 x 1024
matmul-and-sort inside ``bench.window``, one ``bench.sweep_call`` span
each) and on hand-made intervals."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "tpu_small.xplane.pb"


def test_recorded_trace():
    out = tr.reduce_file(TRACE, chips=1)
    assert out["window_s"] == pytest.approx(0.114029284)
    assert out["busy_s"] == pytest.approx(0.000900526)
    ops = out["breakdown"]["device_ops"]
    assert ops[0][0].startswith("%sort")
    assert sum(t for _, t in ops) == pytest.approx(out["busy_s"], rel=1e-6)
    gaps = out["breakdown"]["idle_gaps"]
    assert len(gaps) == tr.TOP
    assert {name for name, _ in gaps} <= {"bench.sweep_call", "bench.harness"}
    assert gaps[0][1] >= gaps[-1][1] > 0


def test_self_time_of_nested_ops():
    got = tr.self_times([("while", 0, 100), ("a", 10, 30), ("b", 40, 60),
                         ("c", 45, 50), ("z", 200, 210)])
    assert [(n, round(t * 1e9)) for n, t in got] == [
        ("while", 60), ("a", 20), ("b", 15), ("c", 5), ("z", 10)]


def test_busy_gaps_and_names():
    devices = {0: [("x", 10, 20), ("y", 15, 30), ("x", 60, 70)],
               1: [("x", 10, 50)]}
    host = [("bench.window", 0, 100), ("bench.call", 0, 45),
            ("bench.call", 50, 100), ("other", 0, 100)]
    out = tr.reduce(devices, host, chips=2)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx((30e-9 + 40e-9) / 2)
    assert out["breakdown"]["idle_gaps"] == [
        ["bench.call", pytest.approx(30e-9)],      # 70-100
        ["bench.call", pytest.approx(30e-9)],      # 30-60, middle at 45
        ["bench.call", pytest.approx(10e-9)]]      # 0-10
    one = tr.reduce(devices, host, chips=1)
    assert one["busy_s"] == pytest.approx(30e-9)


def test_no_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce({0: []}, [], chips=1)
    with pytest.raises(ValueError, match="TPU"):
        tr.reduce({}, [("bench.window", 0, 10)], chips=1)
