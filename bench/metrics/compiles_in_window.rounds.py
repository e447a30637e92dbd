"""Programs built inside the measured window of a rounds cell (backend
compiles and persistent-cache loads, ``bench.counters.CompileCounter``).
The warm-up should leave none."""


def read(run):
    return float(run.compiles_in_window)
