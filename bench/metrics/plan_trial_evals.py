"""Monte-Carlo trial-evaluations a planner decision spent
(``PlanResult.trials_spent``: the race plus the final lower-bound
sweep), averaged over the decisions of the window."""


def read(run):
    ds = run.extra.get("decisions")
    if not ds:
        return None
    return sum(d["trials_spent"] for d in ds) / len(ds)
