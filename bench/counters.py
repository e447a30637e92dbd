"""Counters the benchmark keeps itself: compiles, and the operations a
piece of work requires, counted from its shapes."""
from __future__ import annotations

import math

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Programs this process built, from JAX's monitoring events.  JAX
    records a backend-compile duration for every program it makes ready,
    whether compiled or loaded from the persistent cache (a load also
    records a cache hit).  Either one inside the measured window is a
    program the warm-up missed."""

    def __init__(self):
        import jax
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1


def round_ops_per_eval(family: str, n: int, r: int) -> int:
    """Arithmetic one evaluation of a single round requires under the
    round semantics (arXiv:1810.09992 eq. 1 and Secs. III-V), whatever
    implements it.  Drawing the delays is not counted.

    - slot arrivals: n*r running-sum adds and n*r adds of T2;
    - uncoded (cs/ss/ra): a min over the n*r copies, then the k-th of n
      task arrivals;
    - lb / pcmm: the order statistic of the n*r slot arrivals;
    - pc: the order statistic of the n one-message arrivals.
    An order statistic of L values is counted at L*ceil(log2 L)
    comparisons (a sort).
    """
    def select(L: int) -> int:
        return L * max(1, math.ceil(math.log2(L)))

    slots = n * r
    ops = 2 * slots
    if family in ("cs", "ss", "ra"):
        return ops + slots + select(n)
    if family in ("lb", "pcmm"):
        return ops + select(slots)
    if family == "pc":
        return ops + select(n)
    raise ValueError(f"unknown family {family!r}")
