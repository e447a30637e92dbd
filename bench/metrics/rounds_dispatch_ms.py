"""Self time of the engine's dispatch per ``sweep_rounds`` call, in ms
(span ``engine.dispatch`` under ``engine.rounds``: validation, executable
lookup, keys, scan coordinates and the launch): host time before the
device can start."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "engine.rounds", "engine.dispatch", own=True)
