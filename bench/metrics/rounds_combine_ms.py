"""Time of the engine's combine per ``sweep_rounds`` call, in ms (span
``engine.combine`` under ``engine.rounds``: the device-to-host copies of
the chunk partials and their float64 moments, after the device is
done)."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "engine.rounds", "engine.combine", own=False)
