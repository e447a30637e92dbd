"""Time of the resumable sweep's extensions per decision, in ms (span
``engine.extend``, once a rung: the launch of the sums and samples
scans, the wait for them, and the per-slot slicing and host copies)."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "plan.decide", "engine.extend", own=False)
