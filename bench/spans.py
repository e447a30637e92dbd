"""Per-call readings of the program's own spans and counters
(``repro.obs``) for the per-layer metrics of ``bench/metrics``.

The program keeps span records only while a profiler session is active,
so after a traced window they are exactly the window's calls.  A reading
divides by the root spans of the cell's call (``plan.decide``,
``engine.sweep``: spans opened outside any other) and is None unless
their number equals the calls the window attempted, or where the program
has no ``repro.obs``.
"""
from __future__ import annotations


def _window(run, root: str):
    """(``repro.obs``, the records, the number of ``root`` calls), or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    records = obs.spans()
    calls = sum(1 for s in records if s.name == root and s.parent is None)
    if not calls or calls != run.attempted:
        return None
    return obs, records, calls


def ms_per_call(run, root: str, name: str, own: bool) -> float | None:
    """Milliseconds of span ``name`` per ``root`` call: its self time
    (``own``) or its whole duration."""
    got = _window(run, root)
    if got is None:
        return None
    obs, records, calls = got
    row = obs.summary(records).get(name)
    if row is None:
        return None
    return (row[2] if own else row[1]) * 1e-6 / calls


def count_per_call(run, root: str, counter: str) -> float | None:
    """Counter ``counter`` per ``root`` call."""
    got = _window(run, root)
    if got is None:
        return None
    obs, _, calls = got
    v = obs.counters().get(counter)
    return None if v is None else v / calls
