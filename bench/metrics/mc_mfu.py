"""The whole engine step's share of the chip's peak: the operations the
round semantics requires per evaluation (``bench.counters.
round_ops_per_eval``, counted from the cell's shapes), times evaluations
per second of the window, over the bf16 peak of ``bench/peaks.json``."""
from bench.counters import round_ops_per_eval


def read(run):
    tr = run.traffic
    if tr["driver"] != "sweep" or run.window_s <= 0:
        return None
    n = int(run.config["n"])
    ops_per_trial = sum(round_ops_per_eval(s["family"], n, int(s["r"]))
                        for s in tr["schemes"])
    trials_per_s = run.work / len(tr["schemes"]) / run.window_s
    return 100.0 * ops_per_trial * trials_per_s / run.peaks["bf16_flops_per_s"]
