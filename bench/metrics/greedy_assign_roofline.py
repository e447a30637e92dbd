"""The ``greedy_assign`` kernel's share of its roofline, in %: the least
time the chip could take for the window's kernel calls (the larger of
their operations over the bf16 peak and their bytes over the HBM peak,
``bench.round_counts``, ``bench/peaks.json``) over the kernel's device
self time in the trace (every operation whose name holds
``greedy_assign``).  The kernel runs on the vector unit, on n of the 128
lanes, and the peaks are the matrix unit's and the memory's: a loose
lower bound.  None where no such operation ran in the window."""
from bench.round_counts import greedy_kernel_bytes, greedy_kernel_ops

NAME = "greedy_assign"


def read(run):
    ts, tr = run.trace_summary, run.traffic
    if not ts or tr["driver"] != "rounds":
        return None
    t = sum(s for name, s in ts["op_seconds"].items() if NAME in name)
    if t <= 0:
        return None
    n, chunk = int(run.config["n"]), int(tr["chunk"])
    chunks = -(-int(tr["trials"]) // chunk)
    calls = (run.attempted * chunks * int(tr["rounds"])
             * sum(bool(s.get("adaptive")) for s in tr["schemes"]))
    ops = calls * chunk * greedy_kernel_ops(n)
    nbytes = calls * greedy_kernel_bytes(n, chunk)
    least = max(ops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
