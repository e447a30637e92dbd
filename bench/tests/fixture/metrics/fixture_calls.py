"""Calls made in the window: a metric added as a new file only."""


def read(run):
    return float(run.attempted)
