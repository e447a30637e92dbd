"""The whole adaptive round's share of the chip's peak: the operations
one (trial, scheme, round) evaluation requires (``bench.round_counts.
rounds_ops_per_eval``, counted from the cell's shapes), times the
window's trials per second and rounds, over the bf16 peak of
``bench/peaks.json``.  The work is vector compares, adds and selects, not
matrix products: a loose bound."""
from bench.round_counts import rounds_ops_per_eval


def read(run):
    tr = run.traffic
    if tr["driver"] != "rounds" or run.window_s <= 0:
        return None
    n = int(run.config["n"])
    ops = sum(rounds_ops_per_eval(s, n) for s in tr["schemes"])
    trial_rounds_per_s = run.work / len(tr["schemes"]) / run.window_s
    return 100.0 * ops * trial_rounds_per_s / run.peaks["bf16_flops_per_s"]
