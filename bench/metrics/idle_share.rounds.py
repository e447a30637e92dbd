"""Share of the traced window of a rounds cell in which the chip ran no
operation, from the profiler trace (``bench.trace_reduce``)."""
from bench.trace_reduce import idle_share


def read(run):
    return idle_share(run.trace_summary)
