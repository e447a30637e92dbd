"""Device-to-host bytes the engine copied per planner decision, in MB of
10**6 bytes (counter ``engine.fetched_bytes``: chunk partials and the
per-trial samples of ROADMAP S4)."""
from bench.spans import count_per_call


def read(run):
    v = count_per_call(run, "plan.decide", "engine.fetched_bytes")
    return None if v is None else v / 1e6
