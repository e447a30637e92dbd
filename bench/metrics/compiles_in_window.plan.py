"""Programs built inside the measured window of a planner cell (backend
compiles and persistent-cache loads, ``bench.counters.CompileCounter``).
The warm-up decides under every grid seed of the mix, so it should leave
none."""


def read(run):
    return float(run.compiles_in_window)
