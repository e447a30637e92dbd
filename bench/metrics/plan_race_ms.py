"""Self time of the planner's own race arithmetic per decision, in ms
(span ``plan.race``, once a rung: the per-trial samples, their metric
columns, means and paired differences, and the narrowing)."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "plan.decide", "plan.race", own=True)
