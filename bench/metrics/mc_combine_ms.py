"""Time of the engine's combine per sweep call, in ms (span
``engine.combine``: the device-to-host copies of the chunk partials and
their float64 combine, after the device is done)."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "engine.sweep", "engine.combine", own=False)
