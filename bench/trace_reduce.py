"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
per-operation device time and the longest device idle gaps.

- The window is the benchmark's own host span ``bench.window``.
- A device plane is ``/device:TPU:<i>``; its operations are the events
  of its ``XLA Ops`` line.  Busy time is the union of their intervals
  inside the window, averaged over the first ``chips`` devices.
- An operation's time is its self time: an operation that holds others
  (a ``while`` and the operations of its body) counts only the part its
  children do not cover.  Operations are named by the first
  ``NAME_CHARS`` characters of their HLO text.
- An idle gap is a stretch of the window in which device 0 runs no
  operation.  It is named by the innermost ``bench.*`` host span (other
  than the window) that covers its middle: what the benchmark was doing
  on the host while the device waited.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
TOP = 10
NAME_CHARS = 100


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Merged, sorted [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def self_times(ops):
    """[(name, self seconds)] of operations that nest on one line."""
    out, stack = [], []            # stack: [index into out, end]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= (min(e, stack[-1][1]) - s) * 1e-9
        out.append([name, (e - s) * 1e-9])
        stack.append((len(out) - 1, e))
    return out


def planes_of(path: Path):
    """{device index: [(name, start_ns, end_ns)]} of operations, and the
    host spans [(name, start_ns, end_ns)] named ``bench.*``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if ev.name.startswith("bench.")]
    return devices, host


def reduce(devices: dict, host: list, chips: int) -> dict:
    """Busy and window seconds, the top device operations and the longest
    idle gaps of a window (see the module docstring)."""
    wins = [(s, e) for name, s, e in host if name == WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {WINDOW} span")
    lo, hi = wins[0]
    used = sorted(devices)[:chips]
    if not used:
        raise ValueError("the trace has no TPU device plane")
    busy, op_time = [], defaultdict(float)
    for d in used:
        ivs = _union(_clip([(s, e) for _, s, e in devices[d]], lo, hi))
        busy.append(sum(e - s for s, e in ivs) * 1e-9)
        inside = [(name[:NAME_CHARS], max(s, lo), min(e, hi))
                  for name, s, e in devices[d] if e > lo and s < hi]
        for name, t in self_times(inside):
            op_time[name] += t / len(used)
    # idle gaps of the first device, named by the host span over them
    ivs = _union(_clip([(s, e) for _, s, e in devices[used[0]]], lo, hi))
    gaps, t = [], lo
    for s, e in ivs + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = [(name, s, e) for name, s, e in host if name != WINDOW]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        over = [(e2 - s2, name) for name, s2, e2 in spans if s2 <= mid <= e2]
        named.append([min(over)[1] if over else "bench.harness",
                      (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) * 1e-9,
            "op_seconds": dict(op_time),
            "breakdown": {"device_ops": [[k, v] for k, v in ops[:TOP]],
                          "idle_gaps": named[:TOP]}}


def idle_share(summary) -> float | None:
    """Percent of the window in which the device ran no operation."""
    if not summary or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def reduce_file(path: Path, chips: int) -> dict:
    devices, host = planes_of(path)
    return reduce(devices, host, chips)


def reduce_dir(trace_dir: Path, chips: int) -> dict:
    return reduce_file(find_xplane(trace_dir), chips)
