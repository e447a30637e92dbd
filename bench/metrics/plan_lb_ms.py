"""Time of the planner's final lower-bound sweep per decision, in ms
(span ``plan.lb_sweep``)."""
from bench.spans import ms_per_call


def read(run):
    return ms_per_call(run, "plan.decide", "plan.lb_sweep", own=False)
