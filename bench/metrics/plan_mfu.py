"""The whole planner step's share of the chip's peak: for every decision
of the window, each point's trials times the operations one evaluation
requires (``bench.counters.round_ops_per_eval``), plus the final
lower-bound sweep at the winner's load, over the window and the bf16
peak of ``bench/peaks.json``.  Points are named ``<family>/r<load>/...``
by the planner."""
from bench.counters import round_ops_per_eval


def _ops(name: str, n: int) -> int:
    fam, load = name.split("/")[:2]
    return round_ops_per_eval(fam, n, int(load[1:]))


def read(run):
    ds = run.extra.get("decisions")
    if not ds or run.window_s <= 0:
        return None
    n = int(run.config["n"])
    ops = 0
    for d in ds:
        ops += sum(t * _ops(p, n) for p, t in d["point_trials"].items())
        r = int(d["winner"].split("/")[1][1:])
        ops += d["lb_trials"] * round_ops_per_eval("lb", n, r)
    return 100.0 * ops / run.window_s / run.peaks["bf16_flops_per_s"]
