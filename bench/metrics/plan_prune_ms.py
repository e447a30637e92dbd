"""Self time of the planner's closed-form pruning (span ``plan.prune``:
host numpy, no device work) per decision, in ms."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "plan.decide", "plan.prune", own=True)
