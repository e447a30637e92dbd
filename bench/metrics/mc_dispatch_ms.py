"""Self time of the engine's dispatch per sweep call, in ms (span
``engine.dispatch``: validation, layout, executable lookup, keys, scan
coordinates, parameter transfers and the launch): host time before the
device can start."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "engine.sweep", "engine.dispatch", own=True)
